"""Time under ``udf.wait`` spans (``_run_UDFProject`` on the operator's own thread, waiting for the next morsel whose host stage ran ahead: decode, resize, pad, transfer) in the traced window, per thousand rows: what of the host stage is still on the chip's path."""

from lib import program_spans


def read(run):
    return program_spans.per_krow(run, program_spans.span_s(run, "udf.wait"))
