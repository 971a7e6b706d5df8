"""The routed experts' grouped products' share of their own roofline: the least time the chip could take for the assignments that reached a held expert (the larger of ``expert_matmul_flops`` over the bf16 peak and ``expert_matmul_bytes`` over the HBM peak: every held expert's weights once a layer and call, each assignment's row in and out; the reference's counts; the MXU bounds a prefill call, HBM a decode step) over the device time under the scope ``experts`` in both programs (sort, gather, the routed experts' two grouped products, whatever computes them, and the gated scatter). Assignments of the prefill are reckoned, not counted: the tokens times top-k times the held share the decode steps counted (only decode steps fetch the counters). On a TPU the two products are ``ops/pallas_grouped_matmul.py``'s custom calls, filed under ``experts`` by their scope; XLA's ``ragged-dot`` kernels, which the CPU rehearsal runs, carry no scope and are filed there by kernel name (``lib/lm_scopes.classify``), which assumes the routed experts' are the model's only grouped products."""

from lib import lm_scopes, peaks


def read(run):
    ns, n = lm_scopes.class_ns(run, "experts"), lm_scopes.tokens(run)
    if not ns or n is None or not n.assignments:
        return None
    cfg, ref = run.cell.config, run.cell.reference
    layers, held = cfg["num_hidden_layers"], cfg["num_local_experts"]
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    prefill = n.prefill * cfg["num_experts_per_tok"] * layers * n.held / n.assignments
    least_s = sum(max(ref.expert_matmul_flops(cfg, a) / flops, ref.expert_matmul_bytes(cfg, a, calls * layers, held) / hbm)
                  for a, calls in ((prefill, n.calls), (n.held, n.steps)))
    return 100.0 * least_s / (ns / 1e9)
