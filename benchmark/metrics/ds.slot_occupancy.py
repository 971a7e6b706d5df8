"""Decode slots that held a request, over the window's ``serve.decode_step`` spans: sum(active) / sum(slots): the reader of ``serve.slot_occupancy``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.slot_occupancy")
