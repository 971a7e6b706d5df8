"""Seconds the backend compiled programs (``backend_compile_duration`` without a load from the persistent cache) before the window opened, from the program's compile log: about 0 with every program cached, tens of seconds in a first run."""

from lib import idle_by_span


def read(run):
    return idle_by_span.setup_log_s(run, "compile")
