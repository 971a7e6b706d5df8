"""The selected latent attention's core's share of its own roofline: the least time the chip could take for the scores and weighted values of every *selected* (query, key) pair in every layer (the larger of ``mla_core_flops`` over the bf16 peak and ``mla_core_bytes`` over the HBM peak; the reference's counts: 2 x 128 heads x (192 + 128) a selected pair, each selected latent row read once, queries and outputs once; the MXU bounds a prefill call, HBM a decode step) over the device time under the scope ``mla_core`` in both programs, whatever computes it there (the cache write; the masked form, which expands and scores every block a row holds and so does up to four times this count at the cell's lengths, or a gathered form later). Selected pairs of the prefill are counted (``selected_pairs`` of ``serve.prefill``); a call's row reads at least ``index_topk`` latent rows once its prompt holds as many (row chunks x ``index_topk``, never the bound of a prefill call); a decode token attends min(its prompt and half the answer, ``index_topk``) rows."""

from lib import decoder_scopes, lm_scopes, peaks, program_spans


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    ns, n = decoder_scopes.class_ns(run, cfg["scopes"], "mla_core"), lm_scopes.tokens(run)
    groups = [s[2] for s in program_spans.in_window(run, "serve.prefill") if "selected_pairs" in s[2]]
    if not ns or n is None or not groups or not n.rows:
        return None
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    selected = float(sum(g["selected_pairs"] for g in groups))
    rows_read = float(sum(min(g["row_chunks"] * cfg["index_topk"], g["tokens"] * (g["row_chunks"] / g["rows"] + 1) / 2)
                          for g in groups))
    keys = min(n.prefill / n.rows + cfg["options"]["max_new_tokens"] / 2, cfg["index_topk"])
    least_s = (max(ref.mla_core_flops(cfg, selected) / flops, ref.mla_core_bytes(cfg, n.prefill, rows_read) / hbm)
               + max(ref.mla_core_flops(cfg, n.decode * keys) / flops, ref.mla_core_bytes(cfg, n.decode, n.decode * keys) / hbm))
    return 100.0 * cfg["num_hidden_layers"] * least_s / (ns / 1e9)
