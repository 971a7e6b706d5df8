"""The fullest held expert's tokens in a decode step (over the expert layers) over the mean held expert's: sum(``moe.max_expert_load``) / (sum(``moe.held_assignments``) / (expert layers x experts held)). With ~4 of a step's 64 choices a layer reaching 16 held experts the mean is 0.25 and the fullest holds 1 or 2: what an expert-parallel exchange would wait for."""

from lib import lm_scopes


def read(run):
    n = lm_scopes.tokens(run)
    if n is None or not n.held:
        return None
    cfg = run.cell.config
    return n.max_load / (n.held / ((cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) * cfg["n_routed_experts"]))
