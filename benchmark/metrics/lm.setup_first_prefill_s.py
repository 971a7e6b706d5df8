"""The batcher's first ``serve.prefill`` span (``first``) before the window: tracing and compiling or loading the prefill program, and dispatching its first chunks."""

from lib import lm_scopes, program_spans


def read(run):
    if lm_scopes.aligned(run) is None:
        return None
    return program_spans.setup_span_s(run, "serve.prefill", first=1)
