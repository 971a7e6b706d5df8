"""Of the causal (query, key) pairs of the window's prefilled prompts, the share the selection keeps: sum(``selected_pairs``) / sum(``pairs``) over the window's ``serve.prefill`` spans (``selected_pairs``: sum over prompt positions of min(position + 1, ``index_topk``), counted on the host from the round's lengths). 26% at the cell's lengths; 100% where no prompt passes ``index_topk`` tokens."""

from lib import decoder_scopes


def read(run):
    n = decoder_scopes.counters(run, "serve.prefill", "selected_pairs", "pairs")
    return None if n is None or not n["pairs"] else 100.0 * n["selected_pairs"] / n["pairs"]
