"""Bytes of slot state the batcher holds for one position of one slot: ``state_bytes`` / (``slots`` x ``positions``) of the window's newest ``prompt.run`` span. The latent cache reads 8 attentions x 576 values x 2 B = 9,216; a cache of expanded keys and values (64 heads x (192 + 128)) would read 327,680."""

from lib import lm_scopes, program_spans


def read(run):
    if lm_scopes.aligned(run) is None:
        return None
    held = [s[2] for s in program_spans.in_window(run, "prompt.run") if "state_bytes" in s[2]]
    return held[-1]["state_bytes"] / (held[-1]["slots"] * held[-1]["positions"]) if held else None
