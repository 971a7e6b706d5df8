"""Median device milliseconds of one execution of the decode program (one token for every slot) in the traced window."""

import statistics

from lib import lm_scopes


def read(run):
    got = lm_scopes.programs(run)
    if not got or lm_scopes.DECODE not in got:
        return None
    return statistics.median(got[lm_scopes.DECODE]) / 1e6
