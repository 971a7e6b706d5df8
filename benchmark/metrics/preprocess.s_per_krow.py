"""Self time of the ``preprocess`` spans (``_images_to_numpy``) per thousand rows of the traced window."""

from lib import trace


def read(run):
    if run.events is None or not run.trace_rows or not run.events["spans"].get("preprocess"):
        return None
    return 1000.0 * trace.self_s(run.events, "preprocess", run.span_order) / run.trace_rows
