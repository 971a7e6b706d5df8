"""Time under ``provider.dispatch`` spans (the call of the jitted forward, until it returns) during which no device operation ran, per thousand rows."""

from lib import program_spans


def read(run):
    return program_spans.per_krow(run, program_spans.exposed_s(run, "provider.dispatch"))
