"""The whole model's share of the chip's bf16 peak over the traced prefill and decode executions: the reference's ``step_flops`` of the tokens they processed (true prompt tokens, whose queries the indexer scores against every causal key, the ``index_pairs`` counter of ``serve.prefill``, and which attend the keys selected, its ``selected_pairs``; active decode tokens, each scoring its prompt and half the answer and attending ``index_topk`` of them at most; the head once a sequence and once a decoded token; the routed experts by the held share of assignments the decode steps counted; the shared expert for every token) over their device time times 197 TFLOP/s. Padding, idle slots and the pairs a masked core scores beyond the selected ones are paid for in the time and not counted in the work."""

from lib import decoder_scopes, lm_scopes, peaks


def read(run):
    got, n = lm_scopes.programs(run), lm_scopes.tokens(run)
    pre = decoder_scopes.counters(run, "serve.prefill", "index_pairs", "selected_pairs")
    if not got or n is None or pre is None or not n.rows or not n.prefill or not n.assignments:
        return None
    cfg, ref = run.cell.config, run.cell.reference
    device_s = sum(sum(got.get(p, [])) for p in (lm_scopes.PREFILL, lm_scopes.DECODE)) / 1e9
    if device_s <= 0:
        return None
    held = n.held / n.assignments
    keys = n.prefill / n.rows + cfg["options"]["max_new_tokens"] / 2
    need = (ref.step_flops(cfg, n.prefill, pre["index_pairs"], pre["selected_pairs"], held)
            + ref.step_flops(cfg, n.decode, n.decode * keys, n.decode * min(keys, cfg["index_topk"]), held)
            + ref.head_flops(cfg, n.rows + n.decode))
    return 100.0 * need / (device_s * peaks.peak(run.device_kind, "bf16_flops_per_s"))
