"""Device milliseconds a forward step in operations that belong to the layer norms (``ln1``, ``ln2``, ``ln_pre``, ``ln_post``, ``ln_final``), by the scopes of the compiled forward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    got = scopes.classes(run)
    return None if got is None else got["layernorm"]
