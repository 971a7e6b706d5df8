"""Bytes of row state the batcher holds for one position of one slot: ``kv_bytes`` / (``slots`` x ``positions``) of the window's newest ``prompt.run`` span (the reader of ``oh.kv_bytes_per_token``, under an entry that lists the DeepSeek cell). A layer's latent row (576 values) and indexer key (128 values) in bfloat16 are 1,408 B, five layers 7,040; as read a little more, a slot's rows being held in whole tiles of 128 positions (32,896 for the 32,833 asked for). The indexer's keys are 18% of it."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "oh.kv_bytes_per_token")
