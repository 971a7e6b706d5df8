"""Active slots over slots, summed over the traced window's decode steps: the reader of ``serve.slot_occupancy``, under an entry that lists the Olmo-Hybrid cell (the accepted entry lists granite's alone and may not be edited; PERF.md section 7 (o) asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.slot_occupancy")
