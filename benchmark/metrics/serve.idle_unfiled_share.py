"""The share of the traced window's device idle time that lies under ``prompt.run`` and under none of its ``serve.*`` spans (admission's host arithmetic): the closure of the idle time filed by the program's spans."""

from lib import idle_by_span


def read(run):
    got = idle_by_span.read(run)
    if got is None or not got["idle_total_s"]:
        return None
    return 100.0 * got["idle_s"]["unfiled"] / got["idle_total_s"]
