"""Bytes of key/value rows the batcher holds for one position of one slot: ``kv_bytes`` / (``slots`` x ``positions``) of the window's newest ``prompt.run`` span (the leaves of the slot state the model names as rows). Three attention layers x 2 x 30 heads x 128 x 2 B = 46,080 at the cell's cut (15,360 B a token a layer, where granite's 8 key/value heads hold 4,096 and LongCat's latent row 1,152), and 46,122 as read: a slot's rows are held in whole tiles of 16 positions, 16,464 for the 16,449 asked for."""

from lib import lm_scopes, program_spans


def read(run):
    if lm_scopes.aligned(run) is None:
        return None
    held = [s[2] for s in program_spans.in_window(run, "prompt.run") if "kv_bytes" in s[2]]
    return held[-1]["kv_bytes"] / (held[-1]["slots"] * held[-1]["positions"]) if held else None
