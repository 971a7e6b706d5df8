"""Bytes of row state the batcher holds for one position of one slot: ``kv_bytes`` / (``slots`` x ``positions``) of the window's newest ``prompt.run`` span (the reader of ``oh.kv_bytes_per_token``, under an entry that lists the openPangu cell). A latent row (576 values) in bfloat16 is 1,152 B an attention, over the five main layers and the MTP block's 6,912 B a held position; 7,292.8 as read over the 2,305 positions asked for, a slot's rows being held in whole tiles of 128 (2,432). The kept hidden states are recurrent state, not rows."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "oh.kv_bytes_per_token")
