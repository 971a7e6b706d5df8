"""Time under ``prompt.tokenize`` spans in the traced window, per thousand rows: the reader of ``prompt.tokenize_s_per_krow``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "prompt.tokenize_s_per_krow")
