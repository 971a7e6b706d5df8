"""Share of the device's busy time in the traced window that the prefill program's executions took: the reader of ``serve.prefill_share``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.prefill_share")
