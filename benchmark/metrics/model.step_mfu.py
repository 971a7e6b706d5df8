"""The whole forward step's share of the chip's bf16 peak: operations counted from the configuration's shapes over step time times peak."""

from lib import peaks, trace


def read(run):
    if not trace.has_device(run.events):
        return None
    rows_per_device = run.cell.config["batch_size"] / run.n_devices
    need = run.cell.reference.forward_flops_per_row(run.cell.config) * rows_per_device
    return 100.0 * need / (trace.step_ms(run.events) / 1e3 * peaks.peak(run.device_kind, "bf16_flops_per_s"))
