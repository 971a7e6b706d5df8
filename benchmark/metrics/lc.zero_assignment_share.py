"""Of the router's assignments over the window's decode steps, the share that chose an identity (zero-computation) expert: sum(``moe.zero_assignments``) / sum(``moe.assignments``). 256 of the 768 outputs are such: expected 33% with random weights."""

from lib import decoder_scopes


def read(run):
    n = decoder_scopes.counters(run, "serve.decode_step", "moe.zero_assignments", "moe.assignments")
    return None if n is None or not n["moe.assignments"] else 100.0 * n["moe.zero_assignments"] / n["moe.assignments"]
