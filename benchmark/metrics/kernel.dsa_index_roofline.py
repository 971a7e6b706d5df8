"""The lightning indexer's score product's share of its own roofline: the least time the chip could take for ``I(t, s)`` of every causal (query, key) pair in every layer (the larger of ``index_flops`` over the bf16 peak and ``index_bytes`` over the HBM peak; the reference's counts: 2 x 64 heads x 128 a pair, each held indexer key read once a call, each query's vectors and weights once; the MXU bounds a prefill call, HBM a decode step) over the device time under the scope ``indexer`` in both programs, whatever computes it there (the three index projections, the key norm, the rotary turn and the write of the chunk's keys beside the scores: the least work counts the scores alone, so the share reads lower than the score kernel's own). Prefill pairs are counted (``index_pairs`` of ``serve.prefill``); the rows a group's calls read are reckoned from its mean length and chunks a row, tokens x (chunks + 1) / 2; a decode token scores its prompt and half the answer."""

from lib import decoder_scopes, lm_scopes, peaks, program_spans


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    ns, n = decoder_scopes.class_ns(run, cfg["scopes"], "indexer"), lm_scopes.tokens(run)
    groups = [s[2] for s in program_spans.in_window(run, "serve.prefill") if "index_pairs" in s[2]]
    if not ns or n is None or not groups or not n.rows:
        return None
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    pairs = float(sum(g["index_pairs"] for g in groups))
    rows_read = float(sum(g["tokens"] * (g["row_chunks"] / g["rows"] + 1) / 2 for g in groups))
    keys = n.prefill / n.rows + cfg["options"]["max_new_tokens"] / 2
    least_s = (max(ref.index_flops(cfg, pairs) / flops, ref.index_bytes(cfg, n.prefill, rows_read) / hbm)
               + max(ref.index_flops(cfg, n.decode * keys) / flops, ref.index_bytes(cfg, n.decode, n.decode * keys) / hbm))
    return 100.0 * cfg["num_hidden_layers"] * least_s / (ns / 1e9)
