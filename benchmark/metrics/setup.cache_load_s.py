"""Seconds spent loading programs from the persistent compile cache (``cache_retrieval_time_sec``) before the window opened, from the program's compile log."""

from lib import idle_by_span


def read(run):
    return idle_by_span.setup_log_s(run, "cache_load")
