"""Megabytes of recurrent state the batcher holds for one slot, whatever the length: ``recurrent_bytes`` / ``slots`` / 1e6 of the window's newest ``prompt.run`` span (the leaves of the slot state without a positions axis). Nine linear layers x (30 heads x 192 x 96 float32 = 2,211,840 B of delta-rule state + a conv tail of 3 x 11,520 bfloat16 = 69,120 B) = 20.53 at the cell's cut, against 253 MB of key/value rows a slot an attention layer at 16,449 positions."""

from lib import lm_scopes, program_spans


def read(run):
    if lm_scopes.aligned(run) is None:
        return None
    held = [s[2] for s in program_spans.in_window(run, "prompt.run") if "recurrent_bytes" in s[2]]
    return held[-1]["recurrent_bytes"] / held[-1]["slots"] / 1e6 if held else None
