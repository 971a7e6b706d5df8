"""The fullest held expert's tokens in a decode step (over the layers) over the mean held expert's: sum(``moe.max_expert_load``) / (sum(``moe.held_assignments``) / (layers x experts held)). What an expert-parallel exchange would wait for."""

from lib import lm_scopes


def read(run):
    n = lm_scopes.tokens(run)
    if n is None or not n.held:
        return None
    cfg = run.cell.config
    return n.max_load / (n.held / (cfg["num_hidden_layers"] * cfg["num_local_experts"]))
