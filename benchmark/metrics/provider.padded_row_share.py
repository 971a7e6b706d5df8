"""Rows the forward ran that no one asked for: sum(padded_rows - rows) / sum(padded_rows) over the window's ``provider.pad`` spans."""

from lib import program_spans


def read(run):
    padded = program_spans.counter_sum(run, "provider.pad", "padded_rows")
    rows = program_spans.counter_sum(run, "provider.pad", "rows")
    if not padded or rows is None:
        return None
    return 100.0 * (padded - rows) / padded
