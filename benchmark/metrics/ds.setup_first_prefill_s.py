"""The batcher's first ``serve.prefill`` span (``first``) before the window: tracing and compiling or loading the prefill program: the reader of ``lm.setup_first_prefill_s``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "lm.setup_first_prefill_s")
