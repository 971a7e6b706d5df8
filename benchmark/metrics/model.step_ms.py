"""Device time of one jitted forward: median over the whole executions in the traced window."""

from lib import trace


def read(run):
    if not trace.has_device(run.events):
        return None
    return trace.step_ms(run.events)
