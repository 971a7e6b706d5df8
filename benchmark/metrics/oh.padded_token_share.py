"""The share of the tokens the prefill calls ran that were padding: the reader of ``serve.padded_token_share``, under an entry that lists the Olmo-Hybrid cell (the accepted entry lists granite's alone and may not be edited; PERF.md section 7 (o) asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "serve.padded_token_share")
