"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the lightning indexer (its three projections, the key's LayerNorm, the rotary turn, the write of the indexer's keys and the index scores of every causal pair: scope ``indexer``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'indexer')
