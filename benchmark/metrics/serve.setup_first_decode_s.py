"""The batcher's first ``serve.decode_step`` span (``first``) before the window: tracing and compiling or loading the decode program, its first dispatch and the wait for the first round's prefill."""

from lib import idle_by_span, program_spans


def read(run):
    if idle_by_span.clock(run) is None:
        return None
    return program_spans.setup_span_s(run, "serve.decode_step", first=1)
