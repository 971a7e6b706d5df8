"""Seconds under ``prompt.run`` (one ``ContinuousBatcher.run``) during which no device operation ran, per thousand rows: the admission and decode loop's host time that the device waits for."""

from lib import lm_scopes, program_spans


def read(run):
    if lm_scopes.aligned(run) is None:
        return None
    return program_spans.per_krow(run, program_spans.exposed_s(run, "prompt.run"))
