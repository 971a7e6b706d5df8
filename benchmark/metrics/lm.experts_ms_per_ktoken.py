"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the expert layers (scopes ``router``, ``experts``, ``shared_mlp``), over both programs, by the scopes of their compiled text (``lib/lm_scopes.py``): the router, the shared MLP and, under ``experts``, the routed experts' two grouped products, whatever computes them, with their sort, gather and scatter (on a TPU ``ops/pallas_grouped_matmul.py``'s custom calls carry the scope; XLA's ``ragged-dot`` kernels, which the CPU rehearsal runs, carry none and are filed here by kernel name, on the assumption that the routed experts' are the model's only grouped products)."""

from lib import lm_scopes


def read(run):
    return lm_scopes.per_ktoken_ms(run, lm_scopes.class_ns(run, "experts", "router", "shared_mlp"))
