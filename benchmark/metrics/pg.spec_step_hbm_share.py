"""The speculative step's share of the HBM's peak bandwidth: the bytes one step must read (the reference's ``spec_step_bytes``: every held weight of the main layers, the MTP block and the head, the routed experts by the ``moe.experts_reached`` counter, and every latent row the active slots' queries see by the ``keys`` counter; bfloat16), the mean over the window's ``serve.decode_step`` spans, over the mean device time of one execution of the decode program times 819 GB/s. A step of 64 slots reads ~12.4 GB; what it reads twice, or more than it must, is paid for in the time and not counted."""

from lib import lm_scopes, peaks, program_spans


def read(run):
    got = lm_scopes.programs(run)
    if not got or lm_scopes.DECODE not in got or lm_scopes.aligned(run) is None:
        return None
    steps = [r[2] for r in program_spans.in_window(run, "serve.decode_step")]
    if not steps or not all("keys" in s and "drafted" in s and "moe.experts_reached" in s for s in steps):
        return None
    cfg, ref = run.cell.config, run.cell.reference
    need = sum(ref.spec_step_bytes(cfg, s["moe.experts_reached"], s["keys"], s["drafted"]) for s in steps) / len(steps)
    took = sum(got[lm_scopes.DECODE]) / len(got[lm_scopes.DECODE]) / 1e9
    return 100.0 * need / (took * peaks.peak(run.device_kind, "hbm_bytes_per_s"))
