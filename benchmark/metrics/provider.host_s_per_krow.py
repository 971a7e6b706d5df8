"""Time inside ``provider`` spans (``_chunked_forward``: pad, stage, dispatch, fetch) during which no device operation ran, per thousand rows."""

from lib import trace


def read(run):
    if not trace.has_device(run.events) or not run.trace_rows or not run.events["spans"].get("provider"):
        return None
    return 1000.0 * trace.uncovered_by_device_s(run.events, "provider") / run.trace_rows
