"""Device milliseconds a forward step in operations that belong to the attention core (``.../attn_core/...``: logits, softmax, the product with v), by the scopes of the compiled forward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    got = scopes.classes(run)
    return None if got is None else got["attn_core"]
