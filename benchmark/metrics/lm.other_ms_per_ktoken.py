"""Device milliseconds a thousand tokens processed in everything else that kept the device busy: the traced window's busy time less the four named classes (embedding, residuals and norms between the parts, sampling, state gathers and copies), so that the five sum to the busy time."""

from lib import lm_scopes, trace


def read(run):
    named = lm_scopes.class_ns(run, "mamba", "ssd_scan", "experts", "router", "shared_mlp", "attn", "head")
    if named is None:
        return None
    return lm_scopes.per_ktoken_ms(run, trace.busy_s(run.events) * 1e9 - named)
