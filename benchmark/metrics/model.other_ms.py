"""Device milliseconds a forward step in operations that belong to everything no other class takes (copies, residual adds, patch embedding, pixel normalisation, projection), by the scopes of the compiled forward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    got = scopes.classes(run)
    return None if got is None else got["other"]
