"""1 - (union of device-operation intervals) / traced window."""

from lib import trace


def read(run):
    if not trace.has_device(run.events):
        return None
    return 100.0 * trace.idle_share(run.events)
