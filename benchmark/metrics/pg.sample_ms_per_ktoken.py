"""Device milliseconds a thousand tokens processed (prompt tokens prefilled and tokens the speculative steps emitted) in the speculative step's sampling (a fresh slot's first token, the draft, the rejection sampler's verdict and correction, the token after an accepted draft, the log-probabilities): scope ``sample``, over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'sample')
