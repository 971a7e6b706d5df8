"""Device milliseconds a thousand tokens processed (prompt tokens prefilled and tokens the speculative steps emitted) in the main model's latent attention core (the cache writes, and in the prefill the expansion of cache rows to keys and values with scores and weighted values, in the speculative step the absorbed form over every row a slot holds): scope ``mla_core``, over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'mla_core')
