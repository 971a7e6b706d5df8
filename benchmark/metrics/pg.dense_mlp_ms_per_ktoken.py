"""Device milliseconds a thousand tokens processed (prompt tokens prefilled and tokens the speculative steps emitted) in the main model's dense SwiGLU of the leading layer and the expert layers' shared expert: scope ``dense_mlp``, over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'dense_mlp')
