"""Of the router's assignments over the window's decode steps (active slots x top 8 x expert layers), the share that reached a routed expert this chip holds (16 of 256 outputs, half of group 0; expected 6.25% with random weights): the reader of ``moe.held_assignment_share``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "moe.held_assignment_share")
