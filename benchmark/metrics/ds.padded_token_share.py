"""Tokens the prefill program ran that no prompt held: sum(padded_tokens - tokens) / sum(padded_tokens) over the window's ``serve.prefill`` spans: the reader of ``serve.padded_token_share``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.padded_token_share")
