"""The batcher's first ``serve.prefill`` before the window (``first``: trace, compile or load of the prefill program): the reader of ``lm.setup_first_prefill_s``, under an entry that lists the Olmo-Hybrid cell (the accepted entry lists granite's alone and may not be edited; PERF.md section 7 (o) asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "lm.setup_first_prefill_s")
