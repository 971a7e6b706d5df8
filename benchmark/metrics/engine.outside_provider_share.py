"""Share of the traced window that no ``udf`` span covers: the operator loop, the source or scan, and the consumer."""

from lib import trace


def read(run):
    if run.events is None or not run.events["spans"].get("udf"):
        return None
    outside = trace.subtract([tuple(run.events["window"])], trace.span(run.events, "udf"))
    return 100.0 * trace.total(outside) / 1e9 / trace.window_s(run.events)
