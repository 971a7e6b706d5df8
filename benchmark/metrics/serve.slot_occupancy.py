"""Decode slots that held a request, over the window's ``serve.decode_step`` spans: sum(active) / sum(slots). What a batcher that outlives a UDF call would raise."""

from lib import lm_scopes


def read(run):
    n = lm_scopes.tokens(run)
    return None if n is None or not n.slots else 100.0 * n.active / n.slots
