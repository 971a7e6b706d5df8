"""Of the router's assignments over the window's decode steps (active slots x top-k x layers), the share that reached an expert this chip holds: sum(``moe.held_assignments``) / sum(``moe.assignments``). Half the experts are held: expected 50%."""

from lib import lm_scopes


def read(run):
    n = lm_scopes.tokens(run)
    return None if n is None or not n.assignments else 100.0 * n.held / n.assignments
