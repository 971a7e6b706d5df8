"""Seconds spent tracing programs to jaxprs and lowering them to StableHLO before the window opened (cached or not, a process pays both), from the program's compile log."""

from lib import idle_by_span


def read(run):
    return idle_by_span.setup_log_s(run, "trace", "lower")
