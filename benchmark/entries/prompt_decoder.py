"""The entry ``prompt_decoder``: ``prompt`` over any published decoder the
program has on record.

The call chain the window drives, the refusals, ``take`` and the wrappers are
``entries/prompt_text.py``'s, loaded from that file:

    df.with_column("answer", prompt(col(<doc column>), provider="flax_random",
                   model=<config.model>, batch_size=<config.batch_size>, seed=<seed>,
                   **config.options)).select("id", "answer").iter_partitions()

What this entry brings is ``lowerables``: the two programs a run executes, as
shapes, with the model found through the program's record of decoders
(``daft_tpu/models/decoders.DECODERS``: name -> how a name becomes a
configuration, how parameters are drawn, the model class, the cut's options),
where ``prompt_text`` builds one decoder's configuration by name.

**Adding a further decoder behind ``prompt`` to the benchmark is a
configuration and a reference, and nothing else**: a file under ``configs/``
with ``"entry": "prompt_decoder"``, ``"comparison": "logprob_gap"`` and the
model's name as the program's record has it, and a file under ``reference/``
with ``forward_many`` (and the counts its metrics read); traffic files for
``doc_pool`` and per-layer entries as the cell needs. A program without the
record (an older one) runs no such configuration: its ``prompt`` refuses the
cut's options by name before the query is built, and ``lowerables`` says what
is missing.
"""

from __future__ import annotations

import os

from lib import manifest

_text = manifest.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "prompt_text.py"))

SPANS, SPAN_ORDER = _text.SPANS, _text.SPAN_ORDER
exec_config, build, udf_of, take, n_devices = (
    _text.exec_config, _text.build, _text.udf_of, _text.take, _text.n_devices)


#: What may stay on the device once the program is released: the reference's float32 layer needs the rest.
RELEASED_BYTES_MAX = 64 << 20


def release(handle) -> None:
    """Free the program's parameters and slot state before the reference runs:
    the program's own ``release`` (``daft_tpu/functions/ai._ProtocolUdf``). The
    window closes by abandoning the stream, whose next morsel is then still
    running on the operator's feeder thread and holds the prompter until it
    returns (a partition's time, ~15 s at LongCat's sizes, and the chip is busy
    with it); the program's release waits for that call, ``prompt_text.release``
    does not, and a float32 layer of the reference (5 GB) does not fit beside
    12.8 GB of parameters and state. What is still on the device after it is
    an error here, where it says what it is, and not an allocation that fails
    inside the reference."""
    import jax

    udf_of(handle).release()
    held = sum(a.nbytes for a in jax.live_arrays())
    if held > RELEASED_BYTES_MAX:
        raise RuntimeError(f"entry prompt_decoder: {held / 1e9:.2f} GB of arrays are still on the device after the "
                           f"program was released (at most {RELEASED_BYTES_MAX / 1e9:.2f} GB may be)")


def lowerables(config: dict) -> dict:
    """The two programs a run executes, by the name the device trace gives their
    executions -> (jitted function, argument shapes). The model, its parameters
    and the batcher are made as shapes only (``eval_shape``): nothing is drawn,
    placed or run. The sizes are ``FlaxPrompter``'s (positions = prompt + answer
    + 1) and the batcher's own."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.models.serving import ContinuousBatcher

    try:
        from daft_tpu.ai import flax_provider  # noqa: F401  (importing it fills the record)
        from daft_tpu.models import decoders
    except ImportError as e:
        raise SystemExit(f"entry prompt_decoder: this program keeps no record of decoders ({e})")
    o = config["options"]
    decoder = decoders.DECODERS.get(config["model"])
    if decoder is None:
        raise SystemExit(f"entry prompt_decoder: {config['model']!r} is none of the decoders on record "
                         f"{sorted(decoders.DECODERS)}")
    cfg = decoder.from_name(config["model"], **{k: o[k] for k in decoder.cut_options if k in o})
    model = decoder.model(cfg)
    made = {}

    def state_and_logits():  # the batcher's constructor makes both: here as shapes
        b = made["batcher"] = ContinuousBatcher(
            model, None, num_slots=o["num_slots"], max_prompt_tokens=o["max_prompt_tokens"],
            max_seq_len=o["max_prompt_tokens"] + o["max_new_tokens"] + 1)
        return b.state, b.cur_logits

    params = jax.eval_shape(lambda: decoder.init(cfg, 0)[1])
    state, logits = jax.eval_shape(state_and_logits)
    b = made["batcher"]
    g, of = b.prefill_rows, jax.ShapeDtypeStruct
    return {"jit__prefill_impl": (b._prefill_fn(), (params, state, logits, of((g, b.chunk), jnp.int32), of((g,), jnp.int32),
                                               of((g,), jnp.int32), of((g,), jnp.int32), of((g,), bool))),
            "jit__decode_impl": (b._decode, (params, state, logits, of((b.B,), jnp.int32), of((b.B,), bool),
                                             jax.eval_shape(lambda: jax.random.PRNGKey(0))))}


def lowerable(config: dict):
    """-> (fn, argument shapes): the prefill program, for ``tools/compile_for_v5e.py``."""
    return lowerables(config)["jit__prefill_impl"]
