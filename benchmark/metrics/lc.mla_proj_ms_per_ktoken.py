"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the latent attention's projections (the low-rank query and key/value projections with their norms, the rotary turn, the output projection: scope ``mla_proj``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'mla_proj')
