"""The main model's latent attention core's share of its own roofline, in the prefill and the speculative step: the least time the chip could take for the scores and weighted values of every causal (query, key) pair in every main layer (the larger of ``mla_core_flops`` over the bf16 peak and ``mla_core_bytes`` over the HBM peak; the reference's counts: 2 x 128 heads x (192 + 128) a pair over expanded heads, each held latent row read once a call, queries and outputs once) over the device time under the scope ``mla_core`` in both programs, whatever computes it there (the cache writes; in the prefill the expansion of cache rows to keys and values, which the least work does not count; in the step the absorbed form). The prefill's pairs are ``pairs`` of ``serve.prefill`` and the rows its calls read are reckoned from a group's mean chunks a row, tokens x (chunks + 1) / 2; a step's two verify queries a slot see m + 1 and m + 2 rows (``2 x keys - drafted`` pairs of ``serve.decode_step``) and read ``keys`` rows. The MTP block's attention is under ``mtp`` and counted in neither."""

from lib import decoder_scopes, lm_scopes, peaks, program_spans


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    ns, n = decoder_scopes.class_ns(run, cfg["scopes"], "mla_core"), lm_scopes.tokens(run)
    groups = [s[2] for s in program_spans.in_window(run, "serve.prefill") if "pairs" in s[2]]
    step = decoder_scopes.counters(run, "serve.decode_step", "verify_positions", "keys", "drafted")
    if not ns or n is None or not groups or not n.rows or step is None:
        return None
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    pairs = float(sum(g["pairs"] for g in groups))
    rows_read = float(sum(g["tokens"] * (g["row_chunks"] / g["rows"] + 1) / 2 for g in groups))
    least_s = (max(ref.mla_core_flops(cfg, pairs) / flops, ref.mla_core_bytes(cfg, n.prefill, rows_read) / hbm)
               + max(ref.mla_core_flops(cfg, 2 * step["keys"] - step["drafted"]) / flops,
                     ref.mla_core_bytes(cfg, step["verify_positions"], step["keys"]) / hbm))
    return 100.0 * cfg["num_hidden_layers"] * least_s / (ns / 1e9)
