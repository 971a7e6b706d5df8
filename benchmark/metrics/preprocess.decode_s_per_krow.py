"""Bytes to an RGB image (``open``/``fromarray`` + ``convert``): the ``decode_ns`` of the window's ``image.preprocess`` spans, per thousand rows."""

from lib import program_spans


def read(run):
    ns = program_spans.counter_sum(run, "image.preprocess", "decode_ns")
    return program_spans.per_krow(run, None if ns is None else ns / 1e9)
