"""The Mamba-2 scan's share of its own roofline: the least time the chip could take for the recurrence of every Mamba layer over the tokens the traced window processed (the larger of ``ssd_scan_flops`` over the bf16 peak and ``ssd_scan_bytes`` over the HBM peak: x, B, C, delta and y once a token, each sequence's float32 state read and written once a prefill chunk or decode step; the reference's counts, HBM bounds both) over the device time under the scope ``ssd_scan`` in both programs, whatever implements it: chunked products in prefill, the recurrence in decode. A ``ragged-dot`` kernel is filed under ``experts`` by name, whatever scope called it (``lib/lm_scopes.classify``): a scan written with one would need a rule of its own there."""

from lib import lm_scopes, peaks


def read(run):
    ns, n = lm_scopes.class_ns(run, "ssd_scan"), lm_scopes.tokens(run)
    if not ns or n is None:
        return None
    cfg, ref = run.cell.config, run.cell.reference
    layers = ref.layer_types(cfg).count("mamba")
    flops, hbm = peaks.peak(run.device_kind, "bf16_flops_per_s"), peaks.peak(run.device_kind, "hbm_bytes_per_s")
    least_s = sum(max(ref.ssd_scan_flops(cfg, tokens) / flops, ref.ssd_scan_bytes(cfg, tokens, sequences) / hbm)
                  for tokens, sequences in ((n.prefill, n.row_chunks), (n.decode, n.decode)))
    return 100.0 * layers * least_s / (ns / 1e9)
