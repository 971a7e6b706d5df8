"""Time under ``provider.stage`` spans (``device_put`` of a padded batch) during which no device operation ran, per thousand rows."""

from lib import program_spans


def read(run):
    return program_spans.per_krow(run, program_spans.exposed_s(run, "provider.stage"))
