"""Time under ``udf.pull`` spans (``_run_UDFProject`` taking the next morsel from its child: the source or scan) in the traced window, per thousand rows."""

from lib import program_spans


def read(run):
    return program_spans.per_krow(run, program_spans.span_s(run, "udf.pull"))
