"""Device milliseconds a thousand tokens processed (prefilled and decoded) in full attention's core (the cache write, scores, softmax and weighted values over the blocks of key/value rows held: ``models/decoders.attention_core``, scope ``attn_core``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'attn_core')
