"""Device idle seconds of the traced window under no ``prompt.run`` (tokenizing, the operator's hand-off between two UDF calls), per thousand rows: what a batcher that outlives a morsel would hide."""

from lib import idle_by_span


def read(run):
    return idle_by_span.outside_run_idle_s_per_krow(run)
