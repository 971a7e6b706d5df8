"""Time under ``prompt.tokenize`` spans (the hashing tokenizer over a UDF call's rows) in the traced window, per thousand rows."""

from lib import lm_scopes, program_spans


def read(run):
    if lm_scopes.aligned(run) is None:
        return None
    return program_spans.per_krow(run, program_spans.span_s(run, "prompt.tokenize"))
