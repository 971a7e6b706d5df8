"""The entry ``embed_image``: the call chain the window drives.

    df.with_column("emb", embed_image(col(<image column>), provider="flax_random",
                   model=<config.model>, batch_size=<config.batch_size>, seed=<seed>,
                   **config.options)).select("id", "emb").iter_partitions()

An entry module gives the harness: ``exec_config`` (execution settings of the
query), ``build`` (the query), ``take`` (one partition as numpy, never through
Python objects), ``n_devices`` (devices the parameters occupy: what "per chip"
divides by), ``release`` (frees the program's device state) and ``SPANS`` (the
program's functions that the traced run wraps from outside, innermost last).
"""

from __future__ import annotations

import gc
from typing import Tuple

import numpy as np

#: (module, attribute path, span name). Children come after their parents.
SPANS = [
    ("daft_tpu.functions.ai", "_images_to_numpy", "preprocess"),
    ("daft_tpu.ai.flax_provider", "_chunked_forward", "provider"),
    ("daft_tpu.ai.flax_provider", "_pad_batch", "pad"),
    ("daft_tpu.ai.flax_provider", "_FlaxModelBase.stage_batch", "stage"),
]
#: Span nesting, outermost first: an instant belongs to the last of these that covers it.
SPAN_ORDER = ["udf", "preprocess", "provider", "pad", "stage"]


def exec_config(config: dict) -> dict:
    # The result cache would answer a repeated pool without the device.
    return {"default_morsel_size": config["batch_size"], "result_cache_enabled": False}


def build(traffic, config: dict, seed: int):
    """-> (query DataFrame, handle). The handle is the expression, whose UDF
    holds the provider instance once the first partition has run."""
    from daft_tpu import col
    from daft_tpu.functions.ai import embed_image

    expr = embed_image(col(traffic.column), provider="flax_random", model=config["model"],
                       batch_size=config["batch_size"], seed=seed, **config.get("options", {}))
    return traffic.df.with_column("emb", expr).select("id", "emb"), expr


def udf_of(handle):
    return handle._expr.udf


def take(partition) -> Tuple[np.ndarray, np.ndarray]:
    rb = partition.combined()
    ids, _ = rb.get_column("id").to_numpy_masked()
    emb, _ = rb.get_column("emb").to_numpy_masked()
    return np.asarray(ids), np.asarray(emb)


def n_devices(handle) -> int:
    import jax

    inst = udf_of(handle)._instances[0]
    return len(jax.tree_util.tree_leaves(inst.params)[0].sharding.device_set)


def release(handle) -> None:
    udf_of(handle)._instances.clear()
    gc.collect()


def lowerable(config: dict):
    """-> (fn, argument shapes): the program's jitted forward at the timed batch,
    as shapes only, for ``tools/compile_for_v5e.py`` (nothing is placed or run).
    The parameters are the whole CLIP model's, as ``init_clip_params`` places them."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.models.clip import CLIPConfig, CLIPModel

    cfg = CLIPConfig.from_name(config["model"])
    model = CLIPModel(cfg)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((2, cfg.image_size, cfg.image_size, 3), jnp.uint8),
        jnp.zeros((2, cfg.context_length), jnp.int32))

    def fwd(p, pixels):
        emb = model.apply(p, pixels, method=model.encode_image)
        return emb / jnp.linalg.norm(emb, axis=-1, keepdims=True).clip(1e-6)

    pixels = jax.ShapeDtypeStruct((config["batch_size"], cfg.image_size, cfg.image_size, 3), jnp.uint8)
    return fwd, (params, pixels)
