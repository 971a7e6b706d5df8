"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the final norm and the tied head (scope ``head``), over both programs, by the scopes of their compiled text (``lib/lm_scopes.py``)."""

from lib import lm_scopes


def read(run):
    return lm_scopes.per_ktoken_ms(run, lm_scopes.class_ns(run, "head"))
