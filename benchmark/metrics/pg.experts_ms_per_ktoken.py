"""Device milliseconds a thousand tokens processed (prompt tokens prefilled and tokens the speculative steps emitted) in the main model's routed experts (sort, gather, the two grouped products, the gated gather back): scope ``experts``, over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'experts')
