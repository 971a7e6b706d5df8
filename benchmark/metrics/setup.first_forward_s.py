"""The instance's first ``provider.forward`` span (``first`` = 1): trace, executable load or compile, the wait for the parameters, first run."""

from lib import program_spans


def read(run):
    return program_spans.setup_span_s(run, "provider.forward", first=1)
