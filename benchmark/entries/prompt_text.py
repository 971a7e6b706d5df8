"""The entry ``prompt_text``: the call chain the window drives.

    df.with_column("answer", prompt(col(<doc column>), provider="flax_random",
                   model=<config.model>, batch_size=<config.batch_size>, seed=<seed>,
                   **config.options)).select("id", "answer").iter_partitions()

``config.options`` carry the answer's form (``max_new_tokens``, ``ignore_eos``,
``logprobs``), the batcher's sizes and, for a published model cut to one chip's
share, the cut. The entry refuses by what a configuration needs: a program
whose ``prompt`` hands its prompter no options (no ``PROMPTER_OPTIONS``) would
drop them all and run its default decoder under the configuration's name, so it
is refused when the cell is resolved; an option the program does not take is
refused by name before the query is built.
"""

from __future__ import annotations

import gc
from typing import Tuple

import numpy as np

from daft_tpu.ai import flax_provider

if not hasattr(flax_provider, "PROMPTER_OPTIONS"):
    raise SystemExit("entry prompt_text: this program's prompt hands its prompter no options (daft_tpu.ai."
                     "flax_provider has no PROMPTER_OPTIONS): it cannot run a configuration's and is not asked to")

#: (module, attribute path, span name). Children come after their parents.
SPANS = [
    ("daft_tpu.ai.flax_provider", "FlaxPrompter.prompt", "prompter"),
    ("daft_tpu.models.serving", "ContinuousBatcher.run", "batcher"),
]
#: Span nesting, outermost first: an instant belongs to the last of these that covers it.
SPAN_ORDER = ["udf", "prompter", "batcher"]


def exec_config(config: dict) -> dict:
    # The result cache would answer a repeated pool without the device.
    return {"default_morsel_size": config["batch_size"], "result_cache_enabled": False}


def _expr(column: str, config: dict, seed: int):
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt

    unknown = sorted(set(config["options"]) - set(flax_provider.PROMPTER_OPTIONS))
    if unknown:
        raise SystemExit(f"entry prompt_text: this program's prompt does not take the options {unknown}")
    return prompt(col(column), provider="flax_random", model=config["model"],
                  batch_size=config["batch_size"], seed=seed, **config["options"])


def build(traffic, config: dict, seed: int):
    """-> (query DataFrame, handle). The handle is the expression, whose UDF
    holds the prompter once the first partition has run."""
    expr = _expr(traffic.column, config, seed)
    return traffic.df.with_column("answer", expr).select("id", "answer"), expr


def udf_of(handle):
    return handle._expr.udf


def take(partition) -> Tuple[np.ndarray, tuple]:
    """-> (ids, (offsets, token ids, log-probabilities)): row ``i``'s answer is
    ``[offsets[i], offsets[i + 1])`` of the two flat arrays; nothing passes
    through Python objects."""
    rb = partition.combined()
    ids, _ = rb.get_column("id").to_numpy_masked()
    answer = rb.get_column("answer").to_arrow()
    tokens, logprobs = answer.field("token_ids"), answer.field("logprobs")
    offsets = tokens.offsets.to_numpy()
    return np.asarray(ids), (offsets - offsets[0], tokens.flatten().to_numpy(), logprobs.flatten().to_numpy())


def n_devices(handle) -> int:
    import jax

    inst = udf_of(handle)._instances[0]
    return len(jax.tree_util.tree_leaves(inst.params)[0].sharding.device_set)


def release(handle) -> None:
    udf_of(handle)._instances.clear()
    gc.collect()


def lowerables(config: dict) -> dict:
    """The two programs a run executes, by the name the device trace gives their
    executions -> (jitted function, argument shapes). The model, its parameters
    and the batcher are made as shapes only (``eval_shape``, as
    ``entries/embed_image.py`` does): nothing is drawn, placed or run. The sizes
    are ``FlaxPrompter``'s (positions = prompt + answer + 1) and the batcher's own."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.models import granite_hybrid as gh
    from daft_tpu.models.serving import ContinuousBatcher

    o = config["options"]
    cfg = gh.GraniteHybridConfig.from_name(config["model"], **{k: o[k] for k in gh.CUT_OPTIONS if k in o})
    model = gh.GraniteHybridLM(cfg)
    made = {}

    def state_and_logits():  # the batcher's constructor makes both: here as shapes
        b = made["batcher"] = ContinuousBatcher(
            model, None, num_slots=o["num_slots"], max_prompt_tokens=o["max_prompt_tokens"],
            max_seq_len=o["max_prompt_tokens"] + o["max_new_tokens"] + 1)
        return b.state, b.cur_logits

    params = jax.eval_shape(lambda: gh.init_granite_params(cfg, 0)[1])
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
