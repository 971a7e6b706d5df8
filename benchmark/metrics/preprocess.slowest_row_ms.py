"""The slowest single row of the PIL loop in the traced window (``slowest_row_ns``): one row that waited shows here, all rows slower do not."""

from lib import program_spans


def read(run):
    rows = [s[2]["slowest_row_ns"] for s in program_spans.in_window(run, "image.preprocess")
            if "slowest_row_ns" in s[2]]
    return max(rows) / 1e6 if rows else None
