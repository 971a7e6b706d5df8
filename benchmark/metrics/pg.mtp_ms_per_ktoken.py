"""Device milliseconds a thousand tokens processed (prompt tokens prefilled and tokens the speculative steps emitted) in the multi-token-prediction block, all of it (in the speculative step: its input projection W_eh, its sandwich-normed expert layer over two positions a slot with its own latent cache, and its head; in the prefill: the cache rows of the prompt's pairs): scope ``mtp``, whose parts carry the prefix ``mtp_`` and so no other class, over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'mtp')
