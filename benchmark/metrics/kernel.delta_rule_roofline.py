"""The gated delta rule's share of its own roofline: the least time the chip could take for the recurrence of every linear layer over the tokens the traced window processed (the larger of ``delta_rule_flops`` over the bf16 peak and ``delta_rule_bytes`` over the HBM peak; the reference's counts: the token-by-token recurrence's operations, q, k, v, beta, g and o once a token, each sequence's float32 state read and written once a prefill call or decode step that carries it; HBM bounds both programs) over the device time under the scope ``delta_rule`` in both programs, whatever computes it there: the chunked form with its triangular solve in prefill, one step a slot in decode, a kernel later."""

from lib import decoder_scopes, lm_scopes, peaks


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    ns, n = decoder_scopes.class_ns(run, cfg["scopes"], "delta_rule"), lm_scopes.tokens(run)
    if not ns or n is None:
        return None
    layers = ref.layer_types(cfg).count("linear_attention")
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    least_s = sum(max(ref.delta_rule_flops(cfg, tokens) / flops, ref.delta_rule_bytes(cfg, tokens, sequences) / hbm)
                  for tokens, sequences in ((n.prefill, n.row_chunks), (n.decode, n.decode)))
    return 100.0 * layers * least_s / (ns / 1e9)
