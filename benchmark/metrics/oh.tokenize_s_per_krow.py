"""Seconds a thousand rows under ``prompt.tokenize``: the reader of ``prompt.tokenize_s_per_krow``, under an entry that lists the Olmo-Hybrid cell (the accepted entry lists granite's alone and may not be edited; PERF.md section 7 (o) asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "prompt.tokenize_s_per_krow")
