"""Median device milliseconds of one execution of the decode program (one token for every slot) in the traced window: the reader of ``serve.decode_step_ms``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.decode_step_ms")
