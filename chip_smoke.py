"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # on a TPU host; anything else exits non-zero
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny-cpu   # debug the script itself

One process, no child that imports JAX. It drives the engine's main path
(``from_pydict -> with_column(embed_image) -> UDFProject -> Flax forward ->
collect``) at the full width of CLIP ViT-L/14 with seeded random weights,
then the same UDF over encoded JPEGs with its host stage running ahead of
the chip, the text embedder, the prompter (the small decoder, then the tiny
hybrid Mamba-2 / attention / expert decoder with its spans), the relational device path and the
fused Pallas attention kernel against XLA's attention, and checks every
result. Phases run in order and the first failure ends the run: nothing here
turns a device, compile or Pallas failure into a result. On a pass the last
two lines of stdout are JSON objects: the record of the run
(``"chip_smoke": "pass"``, the device, the cache directory and every phase's
timings), then the verdict alone,
``{"ok": true, "device": {"platform", "kind", "count"}}``. The verdict is
printed only after every phase passed on a TPU.

The times it prints are smoke timings — set-up (instantiate + first call,
compilation included) and run (steady calls, ended by the fetch that forces
the device). They are not metrics and are recorded nowhere under that name.

``--tiny-cpu`` runs the same phases on the ``tiny`` configurations with the
Pallas kernel interpreted, to debug this file without a chip. It refuses to
run unless ``JAX_PLATFORMS=cpu``; its last line is the record with
``"chip_smoke": "dry"`` and it prints no verdict.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, Tuple

import numpy as np

# Full-width sizes, and the tiny ones --tiny-cpu swaps in.
FULL = dict(image_model="ViT-L/14", image_px=224, image_rows=1024,
            image_batch=256, embed_dim=768,
            text_model="all-MiniLM-L6-v2", text_rows=1536, text_warm=512,
            text_dim=384, lm_model="default-lm", prompts=16,
            chain_rows=200_000,
            attn_shapes=((8, 257, 16, 64), (8, 197, 12, 64), (8, 256, 4, 128)),
            mla_shape=(4, 512, 64, 2049))
TINY = dict(image_model="tiny", image_px=32, image_rows=40, image_batch=8,
            embed_dim=32,
            text_model="tiny", text_rows=40, text_warm=8, text_dim=64,
            lm_model="tiny-lm", prompts=16, chain_rows=20_000,
            attn_shapes=((2, 257, 4, 64), (2, 197, 2, 64), (2, 130, 1, 128)),
            mla_shape=(4, 128, 2, 517))


def _timed(fn: Callable):
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def _engine_instance(expr):
    """The provider instance the engine created for this expression's UDF
    (one per replica slot; the smoke runs outside any replica scope)."""
    return expr._expr.udf._instances[0]


def _release(expr) -> None:
    """Drop the engine's provider instances, and with them their device
    memory: a cached plan may keep the UDF itself alive."""
    assert expr._expr.udf.release(), "a call of the UDF was still in flight"


def _check_embeddings(emb: np.ndarray, rows: int, dim: int) -> None:
    assert emb.shape == (rows, dim), f"shape {emb.shape} != {(rows, dim)}"
    assert np.isfinite(emb).all(), "non-finite embedding values"
    norms = np.linalg.norm(emb, axis=1)
    assert np.allclose(norms, 1.0, atol=1e-2), \
        f"rows are not unit-norm: [{norms.min()}, {norms.max()}]"


def _min_cosine(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.min(np.sum(a * b, axis=1)))


def _embed_image_df(cfg, imgs: np.ndarray):
    import daft_tpu
    from daft_tpu.datatype import DataType

    px = cfg["image_px"]
    series = daft_tpu.Series.from_numpy(
        imgs.reshape(len(imgs), -1), "img", DataType.image("RGB", px, px))
    return daft_tpu.from_pydict({"img": series})


def phase_a(cfg, imgs: np.ndarray) -> Tuple[dict, np.ndarray, object]:
    """embed_image through the engine. Returns the record, the embeddings and
    the expression (for its engine instance)."""
    from daft_tpu import col
    from daft_tpu.functions.ai import embed_image
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    rows, batch = len(imgs), cfg["image_batch"]
    df = _embed_image_df(cfg, imgs)
    expr = embed_image(col("img"), provider="flax_random",
                       model=cfg["image_model"], batch_size=batch)
    _, setup_s = _timed(lambda: df.limit(batch).with_column("emb", expr)
                        .select("emb").collect())

    def run():
        out = df.with_column("emb", expr).select("emb").collect()
        return np.asarray(out.to_pydict()["emb"], dtype=np.float32)

    began = span_clock_ns()
    emb, run_s = _timed(run)
    _check_embeddings(emb, rows, cfg["embed_dim"])
    # One forward a morsel: of one device batch staged ahead on a TPU, of up
    # to sixteen that the call stages itself on the CPU (--tiny-cpu).
    forwards = [s.count for s in recent_device_spans()
                if s.name == "provider.forward" and s.start_ns >= began]
    assert sum(f["chunks"] for f in forwards) == -(-rows // batch) >= 4
    return {"setup_s": setup_s, "run_s": run_s, "rows": rows,
            "forwards": len(forwards)}, emb, expr


def check_placement(inst, cfg, tiny: bool) -> None:
    """Parameters resident on every device the instance claims, and batches
    dp-sharded over them: one device and no mesh on a one-chip host, every
    visible device under the default dp mesh on a larger one."""
    import jax

    from daft_tpu.profiling import newest_device_span

    devices = list(inst.mesh.devices.flat) if inst.mesh is not None \
        else [jax.devices()[0]]
    assert len(devices) == len(jax.devices()), \
        f"instance claims {len(devices)} of {len(jax.devices())} devices"
    assert newest_device_span("provider.forward").count["n_devices"] == len(devices)
    if not tiny:  # the CPU backend reports no memory statistics
        for d in devices:
            used = d.memory_stats()["bytes_in_use"]
            assert used > 0, f"no parameters resident on {d}"
    px, batch = cfg["image_px"], cfg["image_batch"]
    staged = inst.stage_batch(np.zeros((batch, px, px, 3), np.uint8))
    assert staged.sharding.device_set == set(devices)
    assert staged.addressable_shards[0].data.shape[0] == batch // len(devices)


def phase_a_jpeg(cfg, imgs: np.ndarray, tiny: bool) -> dict:
    """embed_image over two morsels of encoded JPEGs: the host stage of the
    second (decode, resize, pad, transfer) runs while the first is on the
    chip, so its ``provider.stage`` has ended before the first morsel's
    ``provider.fetch`` has. On the CPU the operator keeps the serial loop;
    --tiny-cpu tells the descriptor otherwise, rehearses the same control
    flow and asserts no order of two threads on one CPU."""
    import io

    from PIL import Image

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.ai.flax_provider import _FlaxDescriptor
    from daft_tpu.functions.ai import embed_image
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    batch = cfg["image_batch"]
    jpegs = []
    for img in imgs[:2 * batch]:
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=90)
        jpegs.append(buf.getvalue())
    df = daft_tpu.from_pydict({"jpg": jpegs})
    expr = embed_image(col("jpg"), provider="flax_random",
                       model=cfg["image_model"], batch_size=batch)
    beside_host = _FlaxDescriptor.runs_beside_host
    if tiny:
        _FlaxDescriptor.runs_beside_host = lambda self: True
    try:
        _, setup_s = _timed(lambda: df.limit(batch).with_column("emb", expr)
                            .select("emb").collect())
        began = span_clock_ns()
        out, run_s = _timed(lambda: df.with_column("emb", expr).select("emb").collect())
    finally:
        _FlaxDescriptor.runs_beside_host = beside_host
    emb = np.asarray(out.to_pydict()["emb"], dtype=np.float32)
    _check_embeddings(emb, 2 * batch, cfg["embed_dim"])
    spans = [s for s in recent_device_spans() if s.start_ns >= began]

    def named(name):
        return sorted((s for s in spans if s.name == name), key=lambda s: s.start_ns)

    stages, fetches, forwards = named("provider.stage"), named("provider.fetch"), named("provider.forward")
    assert len(stages) == len(fetches) == len(named("udf.host_stage")) == 2, \
        f"{len(stages)} stages, {len(fetches)} fetches: not two morsels of one batch"
    assert all(f.count.get("staged") == 1 for f in forwards), "a forward staged its own input"
    assert {s.thread for s in stages}.isdisjoint({s.thread for s in fetches})
    ahead_ms = (fetches[0].end_ns - stages[1].end_ns) / 1e6
    assert tiny or ahead_ms > 0, \
        f"the second morsel was staged {-ahead_ms:.1f} ms after the first one's fetch ended"
    ready = [s.count["ready"] for s in named("udf.wait") if "rows" in s.count]
    _release(expr)
    return {"setup_s": setup_s, "run_s": run_s, "rows": 2 * batch,
            "second_stage_before_first_fetch_end_ms": round(ahead_ms, 2), "ready": ready}


def phase_b(cfg) -> dict:
    """embed_text at MiniLM widths over strings of mixed length."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import embed_text

    rng = np.random.default_rng(1)
    rows = cfg["text_rows"]
    texts = [" ".join(f"w{rng.integers(0, 5000)}"
                      for _ in range(int(rng.integers(1, 200))))
             for _ in range(rows)]
    df = daft_tpu.from_pydict({"t": texts})
    expr = embed_text(col("t"), provider="flax_random",
                      model=cfg["text_model"])
    _, setup_s = _timed(lambda: df.limit(cfg["text_warm"])
                        .with_column("emb", expr).select("emb").collect())

    def run():
        out = df.with_column("emb", expr).select("emb").collect()
        return np.asarray(out.to_pydict()["emb"], dtype=np.float32)

    emb, run_s = _timed(run)
    _check_embeddings(emb, rows, cfg["text_dim"])
    _release(expr)
    return {"setup_s": setup_s, "run_s": run_s, "rows": rows}


def phase_c(cfg) -> dict:
    """prompt: prefill with donated caches, the decode loop, retire."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt

    n = cfg["prompts"]
    prompts = [f"question {i}: what is {i} plus {i}?" for i in range(n)]
    expr = prompt(col("p"), provider="flax_random", model=cfg["lm_model"],
                  max_new_tokens=8)
    _, setup_s = _timed(
        lambda: daft_tpu.from_pydict({"p": ["warm up the decoder"]})
        .with_column("a", expr).select("a").collect())

    def run():
        out = daft_tpu.from_pydict({"p": prompts}).with_column("a", expr) \
            .select("a").collect()
        return out.to_pydict()["a"]

    answers, run_s = _timed(run)
    assert len(answers) == n and all(a for a in answers), \
        f"expected {n} non-empty answers, got {answers}"
    steps = _engine_instance(expr)._batcher.decode_steps
    assert steps > 0, "the decode loop never ran"
    _release(expr)
    return {"setup_s": setup_s, "run_s": run_s, "rows": n,
            "decode_steps": steps}


def _prompt_docs(n: int, last: int):
    """n documents of unlike length in words (one hashed token each), the last of ``last`` words."""
    lengths = [6 + 5 * i for i in range(n - 1)] + [last]
    return lengths, [" ".join(f"w{(3 * i + j) % 40}" for j in range(m)) for i, m in enumerate(lengths)]


def phase_c_hybrid(cfg) -> dict:
    """prompt on the tiny hybrid decoder (Mamba-2, attention, a sharded expert
    layer): chunked prefill that carries SSM state, the decode loop, answers
    with log-probabilities, and the serving spans in the ring."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    began = span_clock_ns()  # the ring also holds phase C's spans
    n = cfg["prompts"]
    lengths, docs = _prompt_docs(n, 600)  # the last one takes two of the batcher's 512-token chunks
    expr = prompt(col("p"), provider="flax_random", model="granite-hybrid-tiny",
                  max_new_tokens=6, ignore_eos=True, logprobs=True, num_slots=4, max_prompt_tokens=640,
                  num_hidden_layers=4, expert_shard=[0, 2], vocab_shard=[0, 2])

    def run():
        return daft_tpu.from_pydict({"p": docs}).with_column("a", expr) \
            .select("a").collect().to_pydict()["a"]

    answers, run_s = _timed(run)
    assert len(answers) == n and all(
        len(a["token_ids"]) == len(a["logprobs"]) == 6 and np.isfinite(a["logprobs"]).all()
        and all(0 <= t < 128 for t in a["token_ids"]) for a in answers), answers
    spans = [s for s in recent_device_spans()
             if s.start_ns >= began and s.name.startswith(("serve.", "prompt."))]
    names = {s.name for s in spans}
    assert {"prompt.tokenize", "prompt.run", "serve.prefill", "serve.decode_step", "serve.fetch"} <= names, names
    steps = [s for s in spans if s.name == "serve.decode_step"]
    assert all(0 < s.count["active"] <= s.count["slots"] == 4 and "moe.held_assignments" in s.count
               for s in steps), "decode-step spans lack their counters"
    prefilled = sum(s.count["tokens"] for s in spans if s.name == "serve.prefill")
    assert prefilled == sum(lengths), f"prefilled {prefilled} tokens"
    assert max(s.count["chunks"] for s in spans if s.name == "serve.prefill") == 2, "no prompt ran as two chunks"
    # the path the grouped products of each program took when it traced: the tiny decoder's widths fill no
    # lane tile, so XLA's on any backend (the kernel itself is run by tests/test_pallas_grouped_matmul.py)
    moe = {s.name: s.count.get("moe") for s in spans if s.name in ("serve.prefill", "serve.decode_step")}
    assert set(moe.values()) <= {"grouped", "xla"} and len(moe) == 2, moe
    _release(expr)
    return {"run_s": run_s, "rows": n, "decode_steps": len(steps), "prefill_tokens": prefilled,
            "moe": moe}


def phase_c_olmo(cfg, tiny: bool) -> dict:
    """prompt on the tiny Olmo-Hybrid decoder (gated delta-rule linear attention in
    three layers of four, full attention with QK-norm in the fourth): chunked
    prefill that carries the delta rule's state, the decode loop, answers with
    log-probabilities, ``delta`` noted on both serving spans and both kinds of
    slot state counted on ``prompt.run``; then, on the chip, the kernels over the
    key/value rows (``cache_kernels_check``) and the delta rule's kernel
    (``delta_rule_check``)."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    began = span_clock_ns()
    n = cfg["prompts"]
    lengths, docs = _prompt_docs(n, 600)  # the last one takes two of the batcher's 512-token chunks
    expr = prompt(col("p"), provider="flax_random", model="olmo-hybrid-tiny", max_new_tokens=6, ignore_eos=True,
                  logprobs=True, num_slots=4, max_prompt_tokens=640, num_hidden_layers=4)

    def run():
        return daft_tpu.from_pydict({"p": docs}).with_column("a", expr).select("a").collect().to_pydict()["a"]

    answers, run_s = _timed(run)
    assert len(answers) == n and all(
        len(a["token_ids"]) == len(a["logprobs"]) == 6 and np.isfinite(a["logprobs"]).all()
        and all(0 <= t < 256 for t in a["token_ids"]) for a in answers), answers
    spans = [s for s in recent_device_spans() if s.start_ns >= began and s.name.startswith(("serve.", "prompt."))]
    prefilled = sum(s.count["tokens"] for s in spans if s.name == "serve.prefill")
    assert prefilled == sum(lengths), f"prefilled {prefilled} tokens"
    delta = {s.name: s.count.get("delta") for s in spans if s.name in ("serve.prefill", "serve.decode_step")}
    assert delta == {"serve.prefill": "chunked", "serve.decode_step": "recurrent"}, delta
    held = [s.count for s in spans if s.name == "prompt.run"][-1]
    assert held["kv_bytes"] > 0 and held["recurrent_bytes"] > 0 and held["kv_bytes"] + held["recurrent_bytes"] == held["state_bytes"], held
    _release(expr)
    out = {"run_s": run_s, "rows": n, "prefill_tokens": prefilled, "delta": delta,
           "kv_bytes": held["kv_bytes"], "recurrent_bytes": held["recurrent_bytes"]}
    if not tiny:
        out["cache_kernels"] = cache_kernels_check()
    out["delta_rule"] = delta_rule_check(tiny)
    return out


def delta_rule_check(tiny: bool) -> dict:
    """The delta rule's kernel (``ops/pallas_delta_rule.py``) against the XLA form
    it stands beside (``olmo_hybrid.gated_delta_chunked``) at the published head
    sizes (96 | 192, chunks of 64; on the chip three rows of 512 steps x 30 heads
    from a carried state: a full row, one that ends inside a chunk, one that is
    all padding and must return its state to the bit; under --tiny-cpu two heads
    and 128 steps, interpreted): the largest |difference| of o and of the state.
    Then, on the chip, the tiny decoder with chunks of 64 steps (the published
    model's, and the chunk the kernel serves) through the batcher: the prefill
    span must read ``delta`` = ``fused``, the decode span ``recurrent``."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from daft_tpu.models import olmo_hybrid as oh
    from daft_tpu.models.serving import ContinuousBatcher, Request
    from daft_tpu.ops import pallas_delta_rule as pdr
    from daft_tpu.profiling import newest_device_span

    B, T, H, dk, dv, C = (2, 128, 2, 96, 192, 64) if tiny else (3, 512, 30, 96, 192, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = (oh._l2_normalised(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5).astype(jnp.bfloat16)
    k = oh._l2_normalised(jax.random.normal(ks[1], (B, T, H, dk))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, H, dv)).astype(jnp.bfloat16)
    lengths = jnp.asarray([T, T - 37, 0][:B] if not tiny else [T, 0], jnp.int32)
    keep = (jnp.arange(T)[None, :] < lengths[:, None])[..., None]
    g = jnp.where(keep, -jnp.exp(-2.0 + 2.0 * jax.random.normal(ks[3], (B, T, H))), 0.0)
    beta = jnp.where(keep, 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[4], (B, T, H))), 0.0)
    s0 = jax.random.normal(ks[5], (B, H, dv, dk))
    assert tiny or pdr.delta_rule_applies(q.shape, v.shape, jnp.bfloat16, C), "the kernel does not apply at the published widths"
    want_o, want_s = jax.jit(oh.gated_delta_chunked, static_argnums=6)(q, k, v, g, beta, s0, C)
    got_o, got_s = pdr.gated_delta_fused(q, k, v, g, beta, s0, chunk=C, interpret=tiny)
    assert bool(jnp.all(got_s[-1] == s0[-1])), "a row that is all padding did not return its state as it came"
    gaps = {"o": float(jnp.max(jnp.abs(got_o - want_o))), "state": float(jnp.max(jnp.abs(got_s - want_s)))}
    assert max(gaps.values()) <= 1e-4 and float(jnp.std(want_o)) > 0.05, gaps  # both forms are float32 after their inputs
    out = {"shape": [B, T, H, dk, dv, C], "max_abs_diff_vs_chunked": gaps}
    if tiny:
        return out
    model, params = oh.init_olmo_params(dataclasses.replace(oh.OlmoHybridConfig.from_name("olmo-hybrid-tiny"), linear_chunk_size=64), 0)
    b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=700, eos_id=None)
    answers = b.run([Request(tokens=np.arange(2, 2 + n).astype(np.int32) % 256, max_new_tokens=4) for n in (600, 90, 300)])
    delta = {name: newest_device_span(name).count.get("delta") for name in ("serve.prefill", "serve.decode_step")}
    assert delta == {"serve.prefill": "fused", "serve.decode_step": "recurrent"} and all(len(a) == 4 for a in answers), (delta, answers)
    return dict(out, delta=delta)


def cache_kernels_check() -> dict:
    """On the chip: the kernel that writes a chunk's key/value rows against XLA's
    slices and the attention kernel against ``decoders.attention_chunk`` /
    ``attention_core`` at the published head sizes (3 slots x 30 heads x 1,040
    positions x 128, a chunk of 512: a full row, an empty one, one ending inside;
    a decode step with a slot in the last, partial block and an idle one); then
    the tiny decoder with an attention one lane tile wide through the batcher:
    both serving spans must read ``attn`` = ``fused``."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from daft_tpu.models import decoders, olmo_hybrid as oh
    from daft_tpu.models.serving import ContinuousBatcher, Request
    from daft_tpu.ops import pallas_cache_attention as pca, pallas_cache_blocks as pcb
    from daft_tpu.profiling import newest_device_span

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    cache = jax.random.normal(ks[0], (3, 30, 1040, 128)).astype(jnp.bfloat16)
    new = jax.random.normal(ks[1], (3, 512, 30, 128)).astype(jnp.bfloat16)
    slots, starts, lens = jnp.asarray([2, 0, 1], jnp.int32), jnp.full((3,), 512, jnp.int32), jnp.asarray([512, 0, 77], jnp.int32)
    assert pcb.kernels_apply(cache.shape, 512)
    want = pcb.write_blocks_xla(cache, new, slots, starts, lens)
    got = jax.jit(pcb.write_blocks)(cache, new, slots, starts, lens)
    assert bool(jnp.all(got == want)), "the write kernel and XLA's slices differ"
    scale, gaps = 128 ** -0.5, {}
    q = jax.random.normal(ks[2], (3, 512, 30, 1, 128)).astype(jnp.bfloat16)
    assert pca.cache_attention_applies(q.shape, cache.shape, jnp.bfloat16) and pca.cache_attention_applies((3, 1, 30, 1, 128), cache.shape, jnp.bfloat16)
    wide = lambda x: x.astype(jnp.float32)  # noqa: E731  (the reference's rows: the same values, and a product any backend has)
    block = lambda j: (wide(pcb.read_blocks_xla(got, slots, j * 512, 512)), wide(pcb.read_blocks_xla(want, slots, j * 512, 512)))  # noqa: E731
    ref = jax.jit(lambda q: decoders.attention_chunk(q, block, starts[:, None] + jnp.arange(512)[None, :], 2, scale, jnp.bfloat16))(q)
    fused = pca.cache_attention(q, got, want, slots, starts, lens, scale=scale)
    held = np.asarray(lens) > 0
    assert not np.asarray(fused, np.float32)[~held].any(), "a row without a query did not come back as zeros"
    gaps["chunk"] = float(np.abs(np.asarray(fused, np.float32) - np.asarray(ref, np.float32))[held].max())
    q1, positions, active = q[:, :1], jnp.asarray([1030, 511, 7], jnp.int32), jnp.asarray([True, True, False])
    ref = jax.jit(lambda q: decoders.attention_core(q, wide(jnp.swapaxes(got, 1, 2)), wide(jnp.swapaxes(want, 1, 2)), positions[:, None], scale, jnp.bfloat16))(q1)
    fused = pca.cache_attention(q1, got, want, jnp.arange(3), positions, active.astype(jnp.int32), scale=scale)
    gaps["step"] = float(np.abs(np.asarray(fused, np.float32) - np.asarray(ref, np.float32))[:2].max())
    assert not np.asarray(fused, np.float32)[2].any() and max(gaps.values()) <= 3e-2, gaps
    cfg = dataclasses.replace(oh.OlmoHybridConfig.from_name("olmo-hybrid-tiny"), num_attention_heads=2, num_key_value_heads=2, head_dim=128)
    model, params = oh.init_olmo_params(cfg, 0)
    b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=1100, eos_id=None)
    answers = b.run([Request(tokens=np.arange(2, 2 + n).astype(np.int32) % 256, max_new_tokens=4) for n in (600, 90, 1030)])
    attn = {name: newest_device_span(name).count.get("attn") for name in ("serve.prefill", "serve.decode_step")}
    assert attn == {"serve.prefill": "fused", "serve.decode_step": "fused"} and all(len(a) == 4 for a in answers), (attn, answers)
    return {"write": "equal to XLA's slices", "max_abs_diff_vs_xla": {k: round(v, 6) for k, v in gaps.items()}, "attn": attn}


def mla_kernel_check(cfg, tiny: bool) -> dict:
    """The prefill attention kernel alone at LongCat-Flash-Chat's head widths
    (128 + 64 | 128 over a latent of 512; ``mla_shape``: rows, chunk, heads,
    positions a slot), four blocks deep, one row without a query and one that
    ends inside its chunk, against ``mla_core_expanded`` over the same cache.
    On a TPU the rule must select it; under --tiny-cpu it runs interpreted."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from daft_tpu.models import longcat_flash as lc
    from daft_tpu.ops import pallas_mla_attention as pm

    B, T, H, S = cfg["mla_shape"]
    lat, nope, rope, dv = 512, 128, 64, 128
    sizes = SimpleNamespace(kv_lora_rank=lat, qk_nope_head_dim=nope, qk_rope_head_dim=rope, qk_head_dim=nope + rope,
                            v_head_dim=dv, dtype=jnp.bfloat16)
    assert tiny or pm.mla_prefill_applies((B, T, H, nope + rope), jnp.bfloat16, lat, nope, rope, dv), \
        "the prefill kernel does not apply at the published widths"
    rng = np.random.default_rng(5)
    q = jnp.asarray(2.0 * rng.standard_normal((B, T, H, nope + rope)), jnp.bfloat16)
    kv = jnp.asarray(rng.standard_normal((B + 1, lat + rope, S)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((lat, H, nope + dv)) * lat ** -0.5, jnp.bfloat16)
    slots = jnp.asarray([3, 0, 4, 1], jnp.int32)
    starts, lengths = jnp.asarray([3 * T, 3 * T, T, 3 * T], jnp.int32), jnp.asarray([T, 0, T - 28, T], jnp.int32)

    expanded = jax.jit(lambda q, kv, w: lc.mla_expanded_over_slots(sizes, w, q, kv, slots, starts))
    ref, xla_s = _timed(lambda: np.asarray(expanded(q, kv, w), np.float32))
    out, fused_s = _timed(lambda: np.asarray(pm.mla_prefill_attention(
        q, kv, w, slots, starts, lengths, nope=nope, interpret=tiny), np.float32))
    held = np.asarray(lengths) > 0
    assert not out[~held].any(), "a row without a query did not come back as zeros"
    np.testing.assert_allclose(out[held], ref[held], atol=3e-2, rtol=3e-2, err_msg=f"mla_prefill_attention {(B, T, H, S)}")
    return {"shape": [B, T, H, S], "max_abs_diff_vs_expanded": round(float(np.abs(out - ref)[held].max()), 6),
            "first_call_s": {"fused": fused_s, "expanded": xla_s}}


def phase_c_longcat(cfg, tiny: bool) -> dict:
    """prompt on the tiny LongCat-Flash decoder (latent attention with a rotary
    part, double layers, identity experts, a sharded expert layer): chunked
    prefill over the latent cache (expanded: its widths fill no lane tile), the
    decode loop (absorbed), and what the spans say of both; then the prefill
    kernel alone at the published head widths (``mla_kernel_check``)."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    began = span_clock_ns()
    n = cfg["prompts"]
    lengths, docs = _prompt_docs(n, 1100)  # the last one takes three of the batcher's 512-token chunks
    expr = prompt(col("p"), provider="flax_random", model="longcat-flash-tiny",
                  max_new_tokens=6, ignore_eos=True, logprobs=True, num_slots=4, max_prompt_tokens=1200,
                  num_layers=2, expert_shard=[0, 2], vocab_shard=[0, 2])

    def run():
        return daft_tpu.from_pydict({"p": docs}).with_column("a", expr) \
            .select("a").collect().to_pydict()["a"]

    answers, run_s = _timed(run)
    assert len(answers) == n and all(
        len(a["token_ids"]) == len(a["logprobs"]) == 6 and np.isfinite(a["logprobs"]).all()
        and all(0 <= t < 128 for t in a["token_ids"]) for a in answers), answers
    spans = [s for s in recent_device_spans()
             if s.start_ns >= began and s.name.startswith(("serve.", "prompt."))]
    steps = [s for s in spans if s.name == "serve.decode_step"]
    assert steps and all("moe.zero_assignments" in s.count and s.count["moe.assignments"] == s.count["active"] * 3 * 2
                         for s in steps), "decode-step spans lack their counters"
    prefills = [s for s in spans if s.name == "serve.prefill"]
    assert sum(s.count["tokens"] for s in prefills) == sum(lengths) and max(s.count["chunks"] for s in prefills) == 3
    paths = {s.name: (s.count.get("mla"), s.count.get("moe")) for s in prefills + steps}
    assert paths["serve.prefill"][0] == "expanded" and paths["serve.decode_step"][0] == "absorbed", paths
    held = [s.count for s in spans if s.name == "prompt.run"][-1]
    assert held["state_bytes"] == held["slots"] * held["positions"] * 4 * 16 * 2, held  # four latent caches of 16 values
    _release(expr)
    return {"run_s": run_s, "rows": n, "decode_steps": len(steps), "paths": paths,
            "cache_bytes_per_token": held["state_bytes"] // (held["slots"] * held["positions"]),
            "mla_kernel": mla_kernel_check(cfg, tiny)}


def phase_c_deepseek(cfg, tiny: bool) -> dict:
    """prompt on the tiny DeepSeek-V3.2 decoder (latent attention whose keys an
    indexer selects, YaRN, a grouped sigmoid router beside a shared expert):
    chunked prefill over the latent cache and the indexer's keys with the
    selection biting from position 32 (XLA's loops: its widths fill no lane tile),
    the decode loop (the masked absorbed form), and what the spans say of both;
    then the selection path's two kernels at the published widths, the index
    scores and the prefill attention with the selection as an input, against
    XLA's loops (under --tiny-cpu interpreted, at a narrow shape)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt
    from daft_tpu.models import deepseek_v32 as ds, latent_attention as la
    from daft_tpu.ops import pallas_dsa_index as pi, pallas_mla_attention as pm
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    began = span_clock_ns()
    n = cfg["prompts"]
    lengths, docs = _prompt_docs(n, 1100)
    expr = prompt(col("p"), provider="flax_random", model="deepseek-v32-tiny",
                  max_new_tokens=6, ignore_eos=True, logprobs=True, num_slots=4, max_prompt_tokens=1200,
                  num_layers=3, expert_shard=[0, 2], vocab_shard=[0, 2])
    answers, run_s = _timed(lambda: daft_tpu.from_pydict({"p": docs}).with_column("a", expr)
                            .select("a").collect().to_pydict()["a"])
    assert len(answers) == n and all(
        len(a["token_ids"]) == len(a["logprobs"]) == 6 and np.isfinite(a["logprobs"]).all()
        and all(0 <= t < 128 for t in a["token_ids"]) for a in answers), answers
    spans = [s for s in recent_device_spans() if s.start_ns >= began and s.name.startswith(("serve.", "prompt."))]
    steps = [s for s in spans if s.name == "serve.decode_step"]
    prefills = [s for s in spans if s.name == "serve.prefill"]
    assert steps and all(s.count["moe.assignments"] == s.count["active"] * 3 * 2 for s in steps)
    assert sum(s.count["selected_pairs"] for s in prefills) == ds.selected_pairs(lengths, 32) < sum(s.count["pairs"] for s in prefills)
    paths = {s.name: (s.count.get("dsa"), s.count.get("mla")) for s in prefills + steps}
    assert paths == {"serve.prefill": ("masked", "expanded"), "serve.decode_step": ("masked", "absorbed")}, paths
    held = [s.count for s in spans if s.name == "prompt.run"][-1]
    _release(expr)

    # the two kernels of the selection path against XLA's loops over the same caches
    B, T, H, S = cfg["mla_shape"]
    # 16 index heads at the least: with fewer a key's index score is an exact 0 often enough to tie at a threshold
    Hi, Di, lat, nope, rope, dv = (16, 128, 128, 128, 16, 128) if tiny else (64, 128, 512, 128, 64, 128)
    sizes = dataclasses.replace(ds.DeepseekV32Config.from_name("deepseek-v32-tiny"), kv_lora_rank=lat, qk_nope_head_dim=nope,
                                qk_rope_head_dim=rope, v_head_dim=dv)
    assert tiny or (pi.index_scores_applies((B, T, Hi, Di), jnp.bfloat16)
                    and pm.mla_prefill_applies((B, T, H, nope + rope), jnp.bfloat16, lat, nope, rope, dv)), \
        "the selection path's kernels do not apply at the published widths"
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, T, H, nope + rope)), jnp.bfloat16)
    kv = jnp.asarray(rng.standard_normal((B + 1, lat + rope, S)), jnp.bfloat16)
    w_kvb = jnp.asarray(rng.standard_normal((lat, H, nope + dv)) * lat ** -0.5, jnp.bfloat16)
    qi = jnp.asarray(rng.standard_normal((B, T, Hi, Di)), jnp.bfloat16)
    wi = jnp.asarray(rng.standard_normal((B, T, Hi)) * (Hi * Di) ** -0.5, jnp.float32)
    ik = jnp.asarray(rng.standard_normal((B + 1, Di, S)), jnp.bfloat16)
    slots = jnp.asarray([3, 0, 4, 1], jnp.int32)
    starts, lengths_ = jnp.asarray([3 * T, 3 * T, T, 3 * T], jnp.int32), jnp.asarray([T, 0, T - 28, T], jnp.int32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    keep_k = min(2048, 2 * T)
    want_index = np.asarray(jax.jit(lambda: ds.index_scores_expanded(qi, wi, ik, slots, starts))())
    index, index_s = _timed(lambda: pi.index_scores(qi, wi, ik, slots, starts, lengths_, interpret=tiny))
    for b, blocks in ((0, 4), (2, 2), (3, 4)):
        np.testing.assert_allclose(np.asarray(index)[b, :, :blocks * T], want_index[b, :, :blocks * T], atol=2e-2, rtol=2e-2)
    threshold = ds.kth_threshold(index, positions, keep_k, reach=jnp.max(starts) + T)
    kept = np.asarray((index >= threshold[..., None]) & (jnp.arange(index.shape[-1]) <= positions[..., None]))
    held_rows = np.asarray(lengths_) > 0
    assert (kept.sum(-1)[held_rows] == np.minimum(np.asarray(positions)[held_rows] + 1, keep_k)).all(), "a query kept another count than its top-k"
    masked = np.asarray(jax.jit(lambda: la.expanded_over_slots(sizes, w_kvb, q, kv, slots, starts, 0.1, index >= threshold[..., None]))())
    out, fused_s = _timed(lambda: np.asarray(pm.mla_prefill_attention(
        q, kv, w_kvb, slots, starts, lengths_, nope=nope, interpret=tiny, scale=0.1, index=index, threshold=threshold,
        max_heads=ds.KERNEL_HEADS), np.float32))
    np.testing.assert_allclose(out[held_rows], masked[held_rows], atol=3e-2, rtol=3e-2)
    return {"run_s": run_s, "rows": n, "decode_steps": len(steps), "paths": paths,
            "selected_pair_share": round(sum(s.count["selected_pairs"] for s in prefills) / sum(s.count["pairs"] for s in prefills), 4),
            "row_bytes_per_token": held["kv_bytes"] // (held["slots"] * held["positions"]),
            "kernels": {"shape": [B, T, H, S], "first_call_s": {"index": index_s, "attention": fused_s},
                        "max_abs_diff_vs_masked_loop": round(float(np.abs(out - masked)[held_rows].max()), 6)}}


def phase_d(cfg) -> dict:
    """A q06-shaped float32 chain (filter -> project -> sum) on the device,
    against the same query on the host. The counters are the only way to see
    past the relational path's host fallbacks."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.ops.device_eval import device_eval_metrics

    n = cfg["chain_rows"]
    rng = np.random.default_rng(2)
    df = daft_tpu.from_pydict({
        "x": (rng.random(n, dtype=np.float32) * 10.0),
        "y": rng.random(n, dtype=np.float32)})

    def query():
        return (df.where((col("y") < 0.8) & (col("x") > 5.0))
                .agg((col("x") * col("y")).sum().alias("rev"))
                .to_pydict()["rev"][0])

    before = device_eval_metrics.snapshot()
    with daft_tpu.execution_config_ctx(device_eval=True):
        dev, setup_s = _timed(query)
        dev2, run_s = _timed(query)
    after = device_eval_metrics.snapshot()
    with daft_tpu.execution_config_ctx(device_eval=False,
                                       compiled_eval_enabled=False):
        host = query()
    np.testing.assert_allclose(dev, host, rtol=1e-4)
    np.testing.assert_allclose(dev2, host, rtol=1e-4)
    fused = after["fused_rows"] - before["fused_rows"]
    assert fused > 0, f"nothing ran on the device: {after}"
    assert after["device_errors"] == 0, f"device errors: {after}"
    return {"setup_s": setup_s, "run_s": run_s, "rows": n,
            "fused_rows": fused}


def phase_e(cfg, imgs: np.ndarray, tiny: bool) -> dict:
    """The fused attention kernel against XLA's attention: first alone on
    projection outputs of ``attn_shapes`` (compiled by the TPU compiler;
    interpreted only under --tiny-cpu), then one batch through the image
    forward of a one-chip replica, which selects the kernel by itself on a
    TPU, against the same parameters through the forward told it is
    partitioned, which takes XLA's path. Both times are printed."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.ai.flax_provider import FlaxCLIPImageEmbedder
    from daft_tpu.ops.pallas_attention import fused_attention
    from daft_tpu.parallel.replica import replica_scope
    from daft_tpu.profiling import newest_device_span

    rng = np.random.default_rng(3)
    for B, T, H, D in cfg["attn_shapes"]:
        qkv = jnp.asarray(rng.standard_normal((B, T, 3 * H * D)), jnp.bfloat16)
        q, k, v = (t.reshape(B, T, H, D) for t in jnp.split(qkv, 3, axis=-1))
        np.testing.assert_allclose(
            np.asarray(fused_attention(qkv, H, interpret=tiny), dtype=np.float32),
            np.asarray(jax.nn.dot_product_attention(q, k, v), dtype=np.float32)
            .reshape(B, T, H * D), atol=3e-2, rtol=3e-2,
            err_msg=f"fused_attention {(B, T, H, D)}")

    batch = imgs[:cfg["image_batch"]]

    def setup():
        # A pallas_call is not partitioned over a mesh: the kernel is for
        # one-chip replicas, whatever the host holds.
        with replica_scope(0, jax.devices()[:1]):
            emb = FlaxCLIPImageEmbedder(cfg["image_model"], batch_size=len(batch))
        assert emb.mesh is None
        emb.embed_image(batch)
        xla_model = emb.model.clone(partitioned=True)

        @jax.jit
        def xla_fwd(p, pixels):
            e = xla_model.apply(p, pixels, method=xla_model.encode_image)
            return e / jnp.linalg.norm(e, axis=-1, keepdims=True).clip(1e-6)

        xla = lambda: np.asarray(xla_fwd(emb.params, emb.stage_batch(batch)))
        xla()
        return emb, xla

    (emb, xla), setup_s = _timed(setup)
    out, run_s = _timed(lambda: emb.embed_image(batch))
    attn = newest_device_span("provider.forward").count["attn"]
    assert attn == ("xla" if tiny else "fused"), f"the forward took {attn!r}"
    ref, xla_run_s = _timed(xla)
    _check_embeddings(out, len(batch), cfg["embed_dim"])
    np.testing.assert_allclose(out, ref, atol=5e-3,
                               err_msg="fused forward != XLA forward")
    cos = _min_cosine(out, ref)
    assert cos > 0.999, f"fused forward disagrees with XLA's: min cosine {cos}"
    return {"setup_s": setup_s, "run_s": run_s, "xla_run_s": xla_run_s,
            "rows": len(batch), "attn": attn,
            "max_abs_diff_vs_xla": round(float(np.abs(out - ref).max()), 6),
            "min_cosine_vs_xla": round(cos, 5)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="debug run: tiny configurations on the CPU, Pallas "
                         "interpreted; requires JAX_PLATFORMS=cpu and never "
                         "reports a pass")
    tiny = ap.parse_args(argv).tiny_cpu
    if tiny and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("chip_smoke: --tiny-cpu refuses to run unless JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 2
    cfg = TINY if tiny else FULL

    from daft_tpu.device import (
        describe_devices,
        require_tpu,
        setup_compile_cache,
    )

    cache_dir = setup_compile_cache()
    import jax

    # No chip: require_tpu raises, and nothing runs on the CPU in its place.
    device = describe_devices() if tiny else require_tpu()
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} n_devices={device['count']} "
          f"jax={jax.__version__} compile_cache_dir={cache_dir}", flush=True)

    import daft_tpu
    from daft_tpu import _native

    rng = np.random.default_rng(0)
    px = cfg["image_px"]
    imgs = rng.integers(0, 255, (cfg["image_rows"], px, px, 3), dtype=np.uint8)
    phases: Dict[str, dict] = {}
    current = "start"

    def done(name: str, rec: dict) -> None:
        phases[name] = rec
        print(f"chip_smoke: {name} ok {json.dumps(rec)}", flush=True)

    try:
        # The result cache would answer a repeated query without the device.
        with daft_tpu.execution_config_ctx(result_cache_enabled=False):
            current = "A_embed_image"
            rec, emb, expr = phase_a(cfg, imgs)
            inst = _engine_instance(expr)
            check_placement(inst, cfg, tiny)
            np.testing.assert_allclose(
                emb, inst.embed_image(imgs), atol=1e-3,
                err_msg="engine result != direct embed_image call")
            done(current, rec)
            del inst
            _release(expr)

            current = "A_jpeg_host_stage_ahead"
            done(current, phase_a_jpeg(cfg, imgs, tiny))
            current = "B_embed_text"
            done(current, phase_b(cfg))
            current = "C_prompt"
            done(current, phase_c(cfg))
            current = "C_prompt_hybrid"
            done(current, phase_c_hybrid(cfg))
            current = "C_prompt_longcat"
            done(current, phase_c_longcat(cfg, tiny))
            current = "C_prompt_deepseek"
            done(current, phase_c_deepseek(cfg, tiny))
            current = "C_prompt_olmo"
            done(current, phase_c_olmo(cfg, tiny))
            current = "D_device_chain"
            done(current, phase_d(cfg))
            current = "E_pallas"
            done(current, phase_e(cfg, imgs, tiny))
    except BaseException:
        print(f"chip_smoke: FAILED in phase {current}", file=sys.stderr,
              flush=True)
        raise

    print(json.dumps({
        "chip_smoke": "dry" if tiny else "pass",
        "platform": device["platform"], "device_kind": device["kind"],
        "n_devices": device["count"], "jax": jax.__version__,
        "compile_cache_dir": cache_dir,
        "native_lib_loaded": _native.get_lib() is not None,
        "phases": phases}), flush=True)
    if not tiny:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
