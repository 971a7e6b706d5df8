"""Device milliseconds a thousand tokens processed (prompt tokens prefilled and tokens the speculative steps emitted) in operations *filed* under no named scope (embedding, the sandwich norms and residual adds between the parts, the loop's bookkeeping): not the busy time's remainder, so the classes' sum against the busy time shows what the programs' texts do not cover, over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'other')
