"""The whole model's share of the chip's bf16 peak over the traced prefill and speculative-step executions: the reference's counts of the work they did (the prefill's true prompt tokens through the main layers over their causal ``pairs``, the MTP block's cache rows of the ``mtp_positions`` counter, the head once a prompt; a step's ``verify_positions`` through the main layers, each query seeing its slot's rows up to it (the ``keys`` counter), the MTP block over one position an emitted token (``tokens``), the head over the verify positions and the drafts; the routed experts by the held share of assignments the steps counted) over their device time times 197 TFLOP/s. The MTP block's masked second position, a rejected draft's verify and idle slots are paid for in the time and the rejected draft's verify counted in the work."""

from lib import decoder_scopes, lm_scopes, peaks


def read(run):
    got, n = lm_scopes.programs(run), lm_scopes.tokens(run)
    pre = decoder_scopes.counters(run, "serve.prefill", "pairs", "mtp_positions")
    dec = decoder_scopes.counters(run, "serve.decode_step", "verify_positions", "keys", "drafted", "tokens")
    if not got or n is None or pre is None or dec is None or not n.rows or not n.assignments or not dec["drafted"]:
        return None
    cfg, ref = run.cell.config, run.cell.reference
    device_s = sum(sum(got.get(p, [])) for p in (lm_scopes.PREFILL, lm_scopes.DECODE)) / 1e9
    if device_s <= 0:
        return None
    held, verify, emitted = n.held / n.assignments, dec["verify_positions"], dec["tokens"]
    need = (ref.step_flops(cfg, n.prefill, pre["pairs"], held) + ref.mtp_rows_flops(cfg, pre["mtp_positions"])
            + ref.head_flops(cfg, n.rows)
            + ref.step_flops(cfg, verify, 2 * dec["keys"] - dec["drafted"], held)
            + ref.mtp_flops(cfg, emitted, emitted * dec["keys"] / dec["drafted"], held)
            + ref.head_flops(cfg, verify + dec["drafted"]))
    return 100.0 * need / (device_s * peaks.peak(run.device_kind, "bf16_flops_per_s"))
