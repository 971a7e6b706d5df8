"""Full attention's core's share of its own roofline: the least time the chip could take for the scores and weighted values of every attention layer (the larger of ``attn_core_flops`` over the bf16 peak and ``attn_core_bytes`` over the HBM peak; the reference's counts: every causal (query, key) pair, each held key/value row read once a call, queries and outputs once; the MXU bounds a prefill call, HBM a decode step) over the device time under the scope ``attn_core`` in both programs, whatever computes it there (the cache write; XLA's block loop with a running softmax, ``models/decoders.attention_core``, or a kernel later). Prefill pairs are counted (``pairs`` of ``serve.prefill``); the rows a group's calls read are reckoned from its mean length and chunks a row, tokens x (chunks + 1) / 2; a decode token attends its prompt and half the answer."""

from lib import decoder_scopes, lm_scopes, peaks, program_spans


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    ns, n = decoder_scopes.class_ns(run, cfg["scopes"], "attn_core"), lm_scopes.tokens(run)
    groups = [s[2] for s in program_spans.in_window(run, "serve.prefill") if "pairs" in s[2]]
    if not ns or n is None or not groups or not n.rows:
        return None
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    pairs = float(sum(g["pairs"] for g in groups))
    rows_read = float(sum(g["tokens"] * (g["row_chunks"] / g["rows"] + 1) / 2 for g in groups))
    keys = n.prefill / n.rows + cfg["options"]["max_new_tokens"] / 2
    least_s = (max(ref.attn_core_flops(cfg, pairs) / flops, ref.attn_core_bytes(cfg, n.prefill, rows_read) / hbm)
               + max(ref.attn_core_flops(cfg, n.decode * keys) / flops, ref.attn_core_bytes(cfg, n.decode, n.decode * keys) / hbm))
    return 100.0 * ref.layer_types(cfg).count("full_attention") * least_s / (ns / 1e9)
