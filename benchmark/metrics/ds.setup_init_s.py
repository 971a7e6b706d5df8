"""The ``provider.init_params`` span before the window (the parameters drawn tensor by tensor on the device): the reader of ``lm.setup_init_s``, under an entry that lists the DeepSeek cell (the accepted entry lists other cells and may not be edited; PERF.md section 7 asks a ``benchmark`` PR to merge them)."""

from lib import decoder_scopes

read = decoder_scopes.beside(__file__, "lm.setup_init_s")
