"""The ``provider.place_params`` span before the window: ``device_put`` of the parameter tree (dispatched, not waited for)."""

from lib import program_spans


def read(run):
    return program_spans.setup_span_s(run, "provider.place_params")
