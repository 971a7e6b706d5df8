"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the full-attention layers' projections (q, k, v with the QK-norm, and the output projection: scope ``attn_proj``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'attn_proj')
