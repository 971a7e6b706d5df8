"""Time under ``provider.fetch`` spans (``np.asarray`` of the forward's result) during which no device operation ran, per thousand rows: what is left of the fetch once the forward has ended."""

from lib import program_spans


def read(run):
    return program_spans.per_krow(run, program_spans.exposed_s(run, "provider.fetch"))
