"""Share of the window's morsels that were already prepared when the operator asked for them: sum(``ready``) over the ``udf.wait`` spans that delivered a morsel (those that count ``rows``), over their number."""

from lib import program_spans


def read(run):
    delivered = [s[2] for s in program_spans.in_window(run, "udf.wait") if "rows" in s[2]]
    if not delivered:
        return None
    return 100.0 * sum(c.get("ready", 0) for c in delivered) / len(delivered)
