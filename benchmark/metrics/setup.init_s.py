"""The ``provider.init_params`` span before the window: trace, compile or cache load, and dispatch of the parameters' init program."""

from lib import program_spans


def read(run):
    return program_spans.setup_span_s(run, "provider.init_params")
