"""Of the router's assignments over the window's decode steps (active slots x top-k x layers, identity experts among them), the share that reached a routed expert this chip holds: 16 of 768 outputs, expected 2.1%: the reader of ``moe.held_assignment_share``, under an entry that lists the LongCat cell (the accepted entry lists granite's alone and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge the two)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "moe.held_assignment_share")
