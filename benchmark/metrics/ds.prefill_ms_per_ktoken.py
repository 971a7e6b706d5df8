"""Device milliseconds of the prefill program's executions in the traced window, per thousand prompt tokens prefilled (true tokens: padding is paid for, not counted): the reader of ``serve.prefill_ms_per_ktoken``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.prefill_ms_per_ktoken")
