"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the expert layers (scopes ``router``, ``experts``, ``shared_mlp``), over both programs, by the scopes of their compiled text (``lib/lm_scopes.py``); XLA's ``ragged-dot`` kernels carry no scope and are filed here by kernel name, on the assumption that the routed experts' are the model's only grouped products."""

from lib import lm_scopes


def read(run):
    return lm_scopes.per_ktoken_ms(run, lm_scopes.class_ns(run, "experts", "router", "shared_mlp"))
