"""The fullest held expert's tokens in a decode step (over the layers) over the mean held expert's: sum(``moe.max_expert_load``) / (sum(``moe.held_assignments``) / (layers x experts held)). With ~4 of a step's 192 choices reaching 16 held experts a layer the mean is 0.25 and the fullest holds 1 or 2: what an expert-parallel exchange would wait for."""

from lib import lm_scopes


def read(run):
    n = lm_scopes.tokens(run)
    if n is None or not n.held:
        return None
    cfg = run.cell.config
    return n.max_load / (n.held / (cfg["num_layers"] * cfg["n_routed_experts"]))
