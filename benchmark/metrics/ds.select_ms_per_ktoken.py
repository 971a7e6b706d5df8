"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the selection (each query's ``index_topk``-th largest index score by bisection over the bit pattern, compare-and-count passes over the scores: scope ``select``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'select')
