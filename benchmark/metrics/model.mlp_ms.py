"""Device milliseconds a forward step in operations that belong to the MLP (``.../mlp/...``: fc1, the activation, fc2), by the scopes of the compiled forward (``lib/scopes.py``)."""

from lib import scopes


def read(run):
    got = scopes.classes(run)
    return None if got is None else got["mlp"]
