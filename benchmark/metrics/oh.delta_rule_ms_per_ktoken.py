"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the gated delta rule itself (prefill: the chunked form, its triangular solve and the scan over chunks; decode: one step of the recurrence a slot; scope ``delta_rule``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'delta_rule')
