"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the latent attention's core (the cache write, and either the expansion of cache rows to keys and values with masked scores and weighted values, or the masked absorbed form: scope ``mla_core``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'mla_core')
