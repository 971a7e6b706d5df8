"""Device milliseconds of the prefill program's executions in the traced window, per thousand prompt tokens prefilled (true tokens: padding is paid for, not counted)."""

from lib import lm_scopes


def read(run):
    got, n = lm_scopes.programs(run), lm_scopes.tokens(run)
    if not got or n is None or not n.prefill or lm_scopes.PREFILL not in got:
        return None
    return sum(got[lm_scopes.PREFILL]) / 1e6 / (n.prefill / 1e3)
