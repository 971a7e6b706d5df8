"""The prefill program's share of the device's busy time in the traced window: the reader of ``serve.prefill_share``, under an entry that lists the Olmo-Hybrid cell (the accepted entry lists granite's alone and may not be edited; PERF.md section 7 (o) asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.prefill_share")
