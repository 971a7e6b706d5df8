"""The whole model's share of the chip's bf16 peak over the traced prefill and decode executions: the reference's ``step_flops`` of the tokens they processed (true prompt tokens, each taking one step of the recurrence in every linear layer and attending the keys its prompt's causal pairs give in every attention layer: the ``pairs`` counter of ``serve.prefill``; active decode tokens, each attending its prompt and half the answer; the head once a sequence and once a decoded token) over their device time times 197 TFLOP/s. Padding and idle slots are paid for in the time and not counted in the work."""

from lib import decoder_scopes, lm_scopes, peaks


def read(run):
    got, n = lm_scopes.programs(run), lm_scopes.tokens(run)
    pre = decoder_scopes.counters(run, "serve.prefill", "pairs")
    if not got or n is None or pre is None or not n.rows or not n.prefill:
        return None
    cfg, ref = run.cell.config, run.cell.reference
    device_s = sum(sum(got.get(p, [])) for p in (lm_scopes.PREFILL, lm_scopes.DECODE)) / 1e9
    if device_s <= 0:
        return None
    need = (ref.step_flops(cfg, n.prefill, pre["pairs"] / n.prefill)
            + ref.step_flops(cfg, n.decode, n.prefill / n.rows + cfg["options"]["max_new_tokens"] / 2)
            + ref.head_flops(cfg, n.rows + n.decode))
    return 100.0 * need / (device_s * peaks.peak(run.device_kind, "bf16_flops_per_s"))
