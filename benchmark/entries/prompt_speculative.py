"""The entry ``prompt_speculative``: ``prompt`` over a published decoder the
program has on record that drafts its own tokens and verifies them a step at a
time (``daft_tpu/models/decoders.DECODERS``, its model declaring
``draft_length``).

The call chain, the wrappers, the refusals and ``release`` are
``entries/prompt_decoder.py``'s (and so ``entries/prompt_text.py``'s), loaded
from those files; with ``logprobs``, ``prompt`` over a decoder that drafts adds
the drafts to its answers. What this entry brings:

* ``take``: a partition as ``(ids, (offsets, token ids, log-probabilities,
  draft offsets, draft positions, draft ids, draft log-probabilities))``: the
  answer struct also holds each row's drafts (where each stood in the answer, its
  id, log q under the drafting block), which ``comparisons/spec_logprob_gap.py``
  holds against the reference's drafting block.
* ``build`` refuses, before the query is built, a program whose record has no
  such decoder under the configuration's name (an older program: its ``prompt``
  would refuse the cut's options only when the first partition runs).
* ``lowerables``: the two programs a run executes, as shapes: the prefill chunk
  and the speculative step, which takes each slot's last two tokens, how many
  pairs its drafting block has not run and whether its first token is still to
  be drawn.
"""

from __future__ import annotations

import os
from typing import Tuple

import numpy as np

from lib import manifest

_decoder = manifest.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "prompt_decoder.py"))

SPANS, SPAN_ORDER = _decoder.SPANS, _decoder.SPAN_ORDER
exec_config, udf_of, n_devices, release = _decoder.exec_config, _decoder.udf_of, _decoder.n_devices, _decoder.release


def _decoder_of(config: dict):
    from daft_tpu.ai import flax_provider  # noqa: F401  (importing it fills the record)
    from daft_tpu.models import decoders

    decoder = decoders.DECODERS.get(config["model"])
    if decoder is None or not getattr(decoder.model, "draft_length", None):
        raise SystemExit(f"entry prompt_speculative: {config['model']!r} is no decoder on record that drafts its own "
                         f"tokens (the record holds {sorted(decoders.DECODERS)})")
    return decoder


def build(traffic, config: dict, seed: int):
    _decoder_of(config)
    return _decoder.build(traffic, config, seed)


def take(partition) -> Tuple[np.ndarray, tuple]:
    """-> (ids, (offsets, token ids, log-probabilities, draft offsets, draft positions, draft ids, draft
    log-probabilities)): row ``i``'s answer is ``[offsets[i], offsets[i + 1])`` of the two flat arrays after them,
    its drafts ``[draft offsets[i], draft offsets[i + 1])`` of the three after those."""
    rb = partition.combined()
    ids, _ = rb.get_column("id").to_numpy_masked()
    answer = rb.get_column("answer").to_arrow()
    tokens, drafts = answer.field("token_ids"), answer.field("draft_ids")
    offsets, draft_offsets = tokens.offsets.to_numpy(), drafts.offsets.to_numpy()
    return np.asarray(ids), (offsets - offsets[0], tokens.flatten().to_numpy(), answer.field("logprobs").flatten().to_numpy(),
                             draft_offsets - draft_offsets[0], answer.field("draft_positions").flatten().to_numpy(),
                             drafts.flatten().to_numpy(), answer.field("draft_logprobs").flatten().to_numpy())


def lowerables(config: dict) -> dict:
    """The two programs a run executes, by the name the device trace gives their executions -> (jitted function,
    argument shapes), made as shapes only (``eval_shape``): the sizes are ``FlaxPrompter``'s (positions = prompt +
    answer + 1) and the batcher's own."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.models.serving import ContinuousBatcher

    decoder = _decoder_of(config)
    o = config["options"]
    cfg = decoder.from_name(config["model"], **{k: o[k] for k in decoder.cut_options if k in o})
    model = decoder.model(cfg)
    made = {}

    def state_and_logits():  # the batcher's constructor makes both: here as shapes
        b = made["batcher"] = ContinuousBatcher(
            model, None, num_slots=o["num_slots"], max_prompt_tokens=o["max_prompt_tokens"],
            max_seq_len=o["max_prompt_tokens"] + o["max_new_tokens"] + 1, temperature=o.get("temperature", 0.0))
        return b.state, b.cur_logits

    params = jax.eval_shape(lambda: decoder.init(cfg, 0)[1])
    state, logits = jax.eval_shape(state_and_logits)
    b = made["batcher"]
    g, of = b.prefill_rows, jax.ShapeDtypeStruct
    spec = (of((b.B, 2), jnp.int32), of((b.B,), jnp.int32), of((b.B,), bool))
    return {"jit__prefill_impl": (b._prefill_fn(), (params, state, logits, of((g, b.chunk), jnp.int32), of((g,), jnp.int32),
                                                    of((g,), jnp.int32), of((g,), jnp.int32), of((g,), bool))),
            "jit__decode_impl": (b._decode, (params, state, logits, of((b.B,), jnp.int32), of((b.B,), bool),
                                             jax.eval_shape(lambda: jax.random.PRNGKey(0)), None, spec))}


def lowerable(config: dict):
    """-> (fn, argument shapes): the speculative step, for ``tools/compile_for_v5e.py``."""
    return lowerables(config)["jit__decode_impl"]
