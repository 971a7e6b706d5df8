"""Device milliseconds a thousand tokens processed (prefilled and decoded) in operations *filed* under no named scope (embedding, residual adds and norms between the parts, sampling, state copies): not the busy time's remainder, so the classes' sum against the busy time shows what the programs' texts do not cover, over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'other')
