"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the latent attention's projections (``W_qa``, ``W_qb``, ``W_kva`` with their norms and the rotary turn, and ``W_o``: scope ``mla_proj``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'mla_proj')
