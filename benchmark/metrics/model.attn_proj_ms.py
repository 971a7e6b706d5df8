"""Device milliseconds a forward step in operations that belong to the attention projections (``.../attn/qkv``, ``.../attn/out``), by the scopes of the compiled forward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    got = scopes.classes(run)
    return None if got is None else got["attn_proj"]
