"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the routed expert branch (scopes ``router``: sigmoid scores, group-limited top-k; ``experts``: sort, gather, the two grouped products, the gated gather back), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'router', 'experts')
