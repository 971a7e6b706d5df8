"""The routed experts' grouped products' share of their own roofline in the expert layers behind the sigmoid router: the least time the chip could take for the assignments that reached a held expert (the larger of ``expert_matmul_flops`` over the bf16 peak and ``expert_matmul_bytes`` over the HBM peak: the weights of every expert reached, once a layer and call, each assignment's row in and out; the reference's counts; HBM bounds both programs here, a held expert seeing ~64 rows a prefill call and under one a decode step) over the device time under the scope ``experts`` in both programs (sort, gather, the two grouped products, the gated gather back), whatever computes them: ``kernel.scmoe_expert_matmul_roofline``'s reckoning with this configuration's keys. The decode steps' assignments and experts reached are counted (``moe.held_assignments``, ``moe.experts_reached``); the prefill's are reckoned: tokens x top-k x expert layers x the held share the decode steps counted, and every held expert reached in every call."""

from lib import decoder_scopes, lm_scopes, peaks


def read(run):
    cfg, ref = run.cell.config, run.cell.reference
    ns, n = decoder_scopes.class_ns(run, cfg["scopes"], "experts"), lm_scopes.tokens(run)
    reached = decoder_scopes.counters(run, "serve.decode_step", "moe.experts_reached")
    if not ns or n is None or reached is None or not n.assignments:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    prefill = n.prefill * cfg["num_experts_per_tok"] * layers * n.held / n.assignments
    least_s = sum(max(ref.expert_matmul_flops(cfg, a) / flops, ref.expert_matmul_bytes(cfg, a, experts) / hbm)
                  for a, experts in ((prefill, n.calls * layers * cfg["n_routed_experts"]),
                                     (n.held, reached["moe.experts_reached"])))
    return 100.0 * least_s / (ns / 1e9)
