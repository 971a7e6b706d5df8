"""Tokens the prefill program ran that no prompt held: sum(padded_tokens - tokens) / sum(padded_tokens) over the window's ``serve.prefill`` spans (ragged last chunks, rows of a call without a prompt)."""

from lib import lm_scopes


def read(run):
    n = lm_scopes.tokens(run)
    return None if n is None or not n.padded else 100.0 * (n.padded - n.prefill) / n.padded
