"""Share of the device's busy time in the traced window that the prefill program's executions took (the rest is decode steps and state copies)."""

from lib import lm_scopes, trace


def read(run):
    got = lm_scopes.programs(run)
    if not got or lm_scopes.PREFILL not in got:
        return None
    return 100.0 * sum(got[lm_scopes.PREFILL]) / 1e9 / trace.busy_s(run.events)
