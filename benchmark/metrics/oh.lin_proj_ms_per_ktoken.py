"""Device milliseconds a thousand tokens processed (prefilled and decoded) in the linear mixers outside the recurrence (the q, k, v, gate, beta and decay projections, the conv with its carried tail, the q / k normalisation, the gated output norm and the output projection: scope ``lin_proj``), over both programs, by the scopes of their compiled text (``lib/decoder_scopes.py``, the classes the configuration names under ``scopes``)."""

from lib import decoder_scopes


def read(run):
    return decoder_scopes.per_ktoken(run, 'lin_proj')
