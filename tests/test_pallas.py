"""The fused attention kernel (``ops/pallas_attention.py``): its arithmetic in
interpret mode on the CPU against ``jax.nn.dot_product_attention`` on the same
``[B, T, 3d]`` input, the rule that selects it, what it leaves in the traced
forward, and its compile for a described v5e chip at the widths the benchmark
runs; and the same compile of the grouped-matmul kernel
(``ops/pallas_grouped_matmul.py``, whose other tests are in
``tests/test_pallas_grouped_matmul.py``) and of LongCat-Flash's prefill
attention (``ops/pallas_mla_attention.py``, ``tests/test_pallas_mla_attention.py``), and SDAR's block step through
``ops/pallas_cache_attention.py`` (``tests/test_pallas_cache_attention.py``). One file, so that the tests that
describe a TPU topology stay with their fixture (see the on-chip-measurement
guide)."""

import collections
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu.models.layers import MultiHeadAttention
from daft_tpu.ops import pallas_attention as pa


def _reference(qkv, num_heads, head_order=None):
    """XLA's path as ``MultiHeadAttention`` takes it; ``head_order`` permutes
    the heads of the result (a wrong column order, for the test of the right one)."""
    B, T, d3 = qkv.shape
    d = d3 // 3
    q, k, v = (t.reshape(B, T, num_heads, d // num_heads)
               for t in jnp.split(qkv, 3, axis=-1))
    out = jax.nn.dot_product_attention(q, k, v)
    if head_order is not None:
        out = out[:, :, list(head_order)]
    return np.asarray(out.reshape(B, T, d), np.float32)


def _qkv(T, num_heads, head_dim, dtype, seed=0, B=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((B, T, 3 * num_heads * head_dim)), dtype)


# -- arithmetic ------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("num_heads,head_dim", [(4, 64), (12, 64), (2, 128)])
@pytest.mark.parametrize("T", [128, 197, 257, 300])
def test_fused_attention_matches_xla(T, num_heads, head_dim, dtype, tol):
    qkv = _qkv(T, num_heads, head_dim, dtype, seed=T + num_heads)
    out = pa.fused_attention(qkv, num_heads, interpret=True)
    assert out.shape == (2, T, num_heads * head_dim) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), _reference(qkv, num_heads),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("num_heads,head_dim", [(4, 64), (4, 32), (2, 128)])
def test_result_lands_in_the_projection_column_order(num_heads, head_dim):
    """Head h of the result is columns [h*head_dim, (h+1)*head_dim) of [B, T, d]:
    the reference with two heads swapped must not pass for it."""
    qkv = _qkv(197, num_heads, head_dim, jnp.float32, seed=7)
    out = np.asarray(pa.fused_attention(qkv, num_heads, interpret=True))
    np.testing.assert_allclose(out, _reference(qkv, num_heads), atol=2e-5, rtol=2e-5)
    swapped = [1, 0] + list(range(2, num_heads))
    assert not np.allclose(out, _reference(qkv, num_heads, swapped), atol=1e-2, rtol=1e-2)


def test_rows_and_heads_do_not_mix():
    """Attention has no term across batch rows or heads: changing one row's one
    head of v changes that row's head of the result and nothing else."""
    qkv = _qkv(130, 4, 64, jnp.float32, seed=3)
    d = 256
    bumped = qkv.at[1, :, 2 * d + 64:2 * d + 128].add(1.0)  # v of row 1, head 1
    a = np.asarray(pa.fused_attention(qkv, 4, interpret=True))
    b = np.asarray(pa.fused_attention(bumped, 4, interpret=True))
    changed = np.abs(a - b) > 1e-6
    assert changed[1, :, 64:128].all()
    changed[1, :, 64:128] = False
    assert not changed.any()


def test_large_scores_do_not_overflow():
    qkv = _qkv(197, 2, 64, jnp.float32, seed=5) * 30.0
    out = np.asarray(pa.fused_attention(qkv, 2, interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _reference(qkv, 2), atol=1e-4, rtol=1e-4)


# -- the rule that selects it ------------------------------------------------------
def test_block_width_takes_the_whole_row_while_it_fits():
    assert pa._block_width(257, 1024, 2) == 1024   # ViT-L/14: all 16 heads a step
    assert pa._block_width(197, 768, 2) == 768     # ViT-B/16
    assert pa._block_width(1024, 1024, 2) == 512   # half a row a step
    narrower = pa._block_width(1024, 4096, 2)
    assert 0 < narrower < 4096 and 4096 % narrower == 0 and narrower % 128 == 0
    assert pa._step_bytes(1024, narrower, 2) <= pa.VMEM_BUDGET < pa._step_bytes(1024, 2 * narrower, 2)
    assert pa._block_width(4096, 1024, 2) == 0     # the score tiles alone exceed it


@pytest.fixture
def on_tpu(monkeypatch):
    """The backend rule answers as on a TPU, and the kernel it then selects runs
    interpreted. ``calls`` counts the kernel's calls."""
    calls = []
    real = pa.fused_attention

    def interpreted(qkv, num_heads):
        calls.append(qkv.shape)
        return real(qkv, num_heads, interpret=True)

    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pa, "fused_attention", interpreted)
    return calls


def _mha(num_heads=2, d=128, T=8, **kw):
    mha = MultiHeadAttention(num_heads=num_heads, dtype=jnp.float32, **kw)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, T, d)), jnp.float32)
    return mha, x, mha.init(jax.random.PRNGKey(0), x)  # init takes XLA's path


def test_unmasked_attention_on_a_tpu_takes_the_kernel(on_tpu):
    mha, x, params = _mha()
    assert on_tpu == []  # init wants shapes only and traces no kernel
    out = mha.apply(params, x)
    assert on_tpu == [(2, 8, 384)]
    xla = mha.clone(partitioned=True).apply(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xla), atol=2e-5, rtol=2e-5)
    assert len(on_tpu) == 1


def test_a_mask_takes_xla(on_tpu):
    mha, x, params = _mha()
    out = mha.apply(params, x, jnp.ones((1, 1, 8, 8), bool))
    assert on_tpu == [] and np.isfinite(np.asarray(out)).all()


def test_cpu_backend_takes_xla(monkeypatch):
    def never(qkv, num_heads):
        raise AssertionError("the kernel was selected on a CPU backend")

    monkeypatch.setattr(pa, "fused_attention", never)
    assert not pa.backend_is_tpu()
    assert not pa.fused_attention_applies((2, 257, 3072), jnp.bfloat16, 16)
    mha, x, params = _mha()
    assert np.isfinite(np.asarray(mha.apply(params, x))).all()


def test_head_dim_that_does_not_fill_lane_tiles_takes_xla(on_tpu):
    mha, x, params = _mha(num_heads=4, d=192)  # head_dim 48
    out = mha.apply(params, x)
    assert on_tpu == [] and np.isfinite(np.asarray(out)).all()
    assert not pa.fused_attention_applies((2, 8, 3 * 192), jnp.float32, 4)
    assert not pa.fused_attention_applies((2, 8, 3 * 64), jnp.float32, 1)    # d is no whole tile
    assert not pa.fused_attention_applies((2, 8, 384), jnp.float16, 2)       # not bf16 or f32
    assert not pa.fused_attention_applies((2, 4096, 3072), jnp.bfloat16, 16)  # beyond the budget
    assert pa.fused_attention_applies((2, 8, 384), jnp.float32, 2)


def test_a_module_told_it_is_partitioned_takes_xla(on_tpu):
    mha, x, params = _mha(partitioned=True)
    out = mha.apply(params, x)
    assert on_tpu == [] and np.isfinite(np.asarray(out)).all()


def test_kernel_failure_propagates(monkeypatch):
    """A kernel that raises when the forward traces: the error leaves
    MultiHeadAttention, and XLA's result is not substituted."""
    mha, x, params = _mha()

    def broken_kernel(qkv, num_heads):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pa, "fused_attention", broken_kernel)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        mha.apply(params, x)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        jax.jit(mha.apply)(params, x)
    # The masked path never takes the kernel.
    out = mha.apply(params, x, jnp.ones((1, 1, 8, 8), bool))
    assert np.isfinite(np.asarray(out)).all()


def test_a_provider_under_a_mesh_tells_the_model(on_tpu):
    """A replica that spans several devices must not reach the bare kernel:
    the provider sets the module's static field, and no option or variable."""
    from daft_tpu.ai.flax_provider import FlaxCLIPImageEmbedder
    from daft_tpu.parallel.replica import replica_scope
    from daft_tpu.profiling import newest_device_span

    assert len(jax.devices()) > 1  # conftest: 8 virtual CPU devices
    px = np.zeros((8, 32, 32, 3), np.uint8)
    meshed = FlaxCLIPImageEmbedder("tiny", batch_size=8)
    assert meshed.mesh is not None and meshed.model.partitioned
    meshed.embed_image(px)
    assert newest_device_span("provider.forward").count["attn"] == "xla"
    with replica_scope(0, jax.devices()[:1]):
        single = FlaxCLIPImageEmbedder("tiny", batch_size=8)
    assert single.mesh is None and not single.model.partitioned
    assert on_tpu == []  # tiny's width is half a lane tile: XLA either way


# -- what the forward holds, and what its span says --------------------------------
def _vit(layers=2):
    """The image tower at one 128-lane tile of width."""
    from daft_tpu.models.clip import CLIPConfig, CLIPImageEncoder
    from daft_tpu.models.layers import init_params

    cfg = dataclasses.replace(CLIPConfig.tiny(), vision_width=128, vision_heads=2,
                              vision_layers=layers, dtype=jnp.float32)
    model = CLIPImageEncoder(cfg)
    px = jnp.asarray(np.random.default_rng(1).integers(0, 255, (4, 32, 32, 3)), jnp.uint8)
    return cfg, model, init_params(model, jax.random.PRNGKey(0), px), px


def test_no_relayout_is_left_between_qkv_and_out(on_tpu):
    """The traced image tower: under each block's ``attn``, outside the two
    projections, the one operation is the kernel, in the ``attn_core`` scope. No
    split, slice, reshape or transpose of the qkv tensor is left."""
    cfg, model, params, px = _vit()
    by_scope = collections.defaultdict(list)

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            scope = "/".join(s for s in (outer, str(eqn.source_info.name_stack)) if s)
            if "jaxpr" in eqn.params and eqn.primitive.name != "pallas_call":
                walk(eqn.params["jaxpr"].jaxpr, scope)  # the jitted kernel wrapper
            else:
                by_scope[scope].append(eqn.primitive.name)

    walk(jax.make_jaxpr(model.apply)(params, px).jaxpr, "")
    attn = {s: prims for s, prims in by_scope.items() if "/attn" in s}
    for i in range(cfg.vision_layers):
        block = f"CLIPImageEncoder/block_{i}/attn"
        assert attn[block + "/attn_core"] == ["pallas_call"]
        assert attn[block + "/qkv"] == attn[block + "/out"] == ["dot_general", "reshape", "add"]
        assert not [s for s in attn if s.startswith(block)
                    and s not in (block + "/attn_core", block + "/qkv", block + "/out")]
    everything = [p for prims in attn.values() for p in prims]
    assert everything.count("pallas_call") == cfg.vision_layers == len(on_tpu)
    assert not {"transpose", "split", "slice", "dynamic_slice", "gather", "concatenate"} & set(everything)
    # and the forward computes what XLA's path computes
    fused = np.asarray(jax.jit(model.apply)(params, px))
    xla = np.asarray(jax.jit(model.clone(partitioned=True).apply)(params, px))
    np.testing.assert_allclose(fused, xla, atol=1e-4, rtol=1e-4)


def test_forward_span_says_which_path_was_traced(on_tpu):
    from daft_tpu.ai import flax_provider
    from daft_tpu.profiling import newest_device_span

    cfg, model, params, px = _vit()
    paths = []
    for partitioned in (False, True):
        fwd = jax.jit(model.clone(partitioned=partitioned).apply)
        for _ in range(2):  # the second call traces nothing and repeats the first's
            flax_provider._chunked_forward(fwd, params, np.asarray(px), 4, cfg.embed_dim)
            paths.append(newest_device_span("provider.forward").count["attn"])
    assert paths == ["fused", "fused", "xla", "xla"]


# -- compiled for the chip, without the chip ----------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,T,d,num_heads", [(512, 257, 1024, 16), (1024, 197, 768, 12)],
                         ids=["vit_l14", "vit_b16"])
def test_kernel_compiles_for_v5e_with_no_copy_at_its_edges(one_chip, B, T, d, num_heads):
    """qkv projection -> kernel -> out projection + residual at the benchmark's
    shapes, compiled by the TPU's compiler (nothing runs): the projection writes
    the layout the kernel reads and ``out`` reads what it writes."""
    def block(x, w_qkv, b_qkv, w_out):
        qkv = jnp.einsum("btd,de->bte", x, w_qkv) + b_qkv
        return x + jnp.einsum("btd,de->bte", pa.fused_attention(qkv, num_heads), w_out)

    def chain(x, w_qkv, b_qkv, w_out):
        return block(block(x, w_qkv, b_qkv, w_out), w_qkv, b_qkv, w_out)

    shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
              for s in ((B, T, d), (d, 3 * d), (3 * d,), (d, d))]
    text = jax.jit(chain).lower(*shapes).compile().as_text()
    entry = text[text.index("ENTRY"):]
    ops = re.findall(r"= \S+ ([a-z\-]+)\(", entry)
    assert ops.count("custom-call") >= 2 and entry.count("tpu_custom_call") == 2
    # the one copy is of the entry argument x, whose device layout is tokens-outermost
    assert ops.count("copy") <= 1 and "transpose" not in ops
    assert f"[{B},{num_heads},{T},{T}]" not in text


@pytest.mark.parametrize("m,k,n,gated,groups", [
    (20480, 4096, 1536, True, 36), (20480, 768, 4096, False, 36), (320, 4096, 1536, True, 36), (320, 768, 4096, False, 36),
    (24576, 6144, 4096, True, 16), (24576, 2048, 6144, False, 16), (192, 6144, 4096, True, 16), (192, 2048, 6144, False, 16)],
    ids=["prefill_w_in", "prefill_w_out", "decode_w_in", "decode_w_out",
         "longcat_prefill_w_in", "longcat_prefill_w_out", "longcat_decode_w_in", "longcat_decode_w_out"])
def test_grouped_matmul_compiles_for_v5e(one_chip, m, k, n, gated, groups):
    """The routed experts' two products of granite-4.0-h-small's prefill call
    (2,048 tokens x top 10) and decode step (32 slots x 10) over the 36 held
    experts, and of LongCat-Flash-Chat's (2,048 x top 12, 16 slots x 12; 16 held
    experts whose ``w_in`` is 50 MB each, so a column block is narrower than one
    expert's), compiled by the TPU's compiler (nothing runs): one custom call
    each, and the first product's result is ``(m, n / 2)`` bfloat16, with no
    float32 ``(m, n)`` beside it."""
    from daft_tpu.ops import pallas_grouped_matmul as gmm

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in (((m, k), jnp.bfloat16), ((groups, k, n), jnp.bfloat16), ((groups,), jnp.int32))]
    compiled = jax.jit(lambda x, w, sizes: gmm.grouped_matmul(x, w, sizes, gated=gated)).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "ragged-dot" not in text
    assert f"bf16[{m},{n // 2 if gated else n}]" in text and f"f32[{m}," not in text
    tm, tn = gmm._tiles(m, k, n // 2 if gated else n, groups, 2, gated)
    assert tn > 0 and gmm._step_bytes(tm, k, tn, 2, gated) <= gmm.VMEM_BUDGET


def test_mla_prefill_attention_compiles_for_v5e_with_no_expansion_and_no_copy_of_the_cache(one_chip):
    """One attention of LongCat-Flash-Chat's prefill call at the cell's shapes
    (4 rows of 512 queries, 64 heads of 128 + 64 | 128 over a latent of 512, 16
    slots of 16,449 positions) between its neighbours: the chunk's rows written
    into the cache, the kernel, the output projection's reshape. Compiled by the
    TPU's compiler (nothing runs): one custom call; no float32 score tensor and
    no expanded keys and values in HBM (the one ``bf16[4,512,64,256]`` is q, each
    head padded to two lane tiles, and no product writes that shape); the cache
    reaches the kernel as the update left it, with no copy of it."""
    from daft_tpu.ops import pallas_mla_attention as pm

    B, T, H, lat, nope, rope, dv = 4, 512, 64, 512, 128, 64, 128
    cache = (16, lat + rope, 16449)

    def attention(q, kv, w_kvb, rows, slots, starts, lengths):
        for b in range(B):  # as ``longcat_flash._mla_prefill`` writes the chunk
            kv = jax.lax.dynamic_update_slice(kv, rows[b][None], (slots[b], 0, starts[b]))
        out = pm.mla_prefill_attention(q, kv, w_kvb, slots, starts, lengths, nope=nope)
        return out.reshape(B, T, H * dv), kv

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, T, H, nope + rope), jnp.bfloat16), (cache, jnp.bfloat16), ((lat, H, nope + dv), jnp.bfloat16),
        ((B, lat + rope, T), jnp.bfloat16), ((B,), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32))]
    text = jax.jit(attention, donate_argnums=(1,)).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "f32[4,64,512,512]" not in text and "f32[4,512,64,256]" not in text
    assert not re.search(r"bf16\[4,512,64,256\]\S* (convolution|dot|fusion)\(.*kind=kOutput", text)
    assert "convolution" not in text and not re.search(r"= \S+ dot\(", text)
    shape = "bf16[%d,%d,%d]" % cache
    assert not re.search(r"= %s\S* (copy|transpose)\(" % re.escape(shape), text)
    assert f"bf16[{B},{T},{H * dv}]" in text
    heads = pm._heads_a_step(T, lat, nope, rope, dv, H, 2)
    assert heads > 0 and pm._step_bytes(T, lat, nope, rope, dv, heads, 2) <= pm.VMEM_BUDGET


def test_deepseeks_index_kernel_and_selected_attention_compile_for_v5e(one_chip):
    """One layer's selection path of DeepSeek-V3.2-Exp's prefill call at the cell's shapes (4 rows of 512 queries,
    64 index heads of 128, 128 heads of 128 + 64 | 128 over a latent of 512, 8 slots of 32,896 positions): the index
    kernel, the threshold search and the prefill kernel with the selection as an input, compiled by the TPU's
    compiler (nothing runs): two custom calls; no (heads, 512, 512) score tensor of either in HBM; neither cache
    copied; the search reads the (4, 512, 33,280) scores and writes no tensor of that size but its ordered copy."""
    from daft_tpu.models import deepseek_v32 as ds
    from daft_tpu.ops import pallas_dsa_index as pi
    from daft_tpu.ops import pallas_mla_attention as pm

    B, T, H, Hi, Di, lat, nope, rope, dv, slots_n, P = 4, 512, 128, 64, 128, 512, 128, 64, 128, 8, 32896
    cfg = ds.DeepseekV32Config.from_name("DeepSeek-V3.2-Exp", num_layers=5, expert_shard=(0, 16), vocab_shard=(0, 8))

    def select_and_attend(q, kv, w_kvb, qi, w, ik, slots, starts, lengths):
        index = pi.index_scores(qi, w, ik, slots, starts, lengths)
        threshold = ds.kth_threshold(index, starts[:, None] + jnp.arange(T)[None, :], cfg.index_topk, reach=jnp.max(starts) + T)
        return pm.mla_prefill_attention(q, kv, w_kvb, slots, starts, lengths, nope=nope, scale=cfg.softmax_scale,
                                        index=index, threshold=threshold, max_heads=ds.KERNEL_HEADS)

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, T, H, nope + rope), jnp.bfloat16), ((slots_n, lat + rope, P), jnp.bfloat16), ((lat, H, nope + dv), jnp.bfloat16),
        ((B, T, Hi, Di), jnp.bfloat16), ((B, T, Hi), jnp.float32), ((slots_n, Di, P), jnp.bfloat16),
        ((B,), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32))]
    text = jax.jit(select_and_attend).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert f"f32[{B},{Hi},{T},{T}]" not in text and f"f32[{B},{H},{T},{T}]" not in text
    for cache in ((slots_n, lat + rope, P), (slots_n, Di, P)):
        assert not re.search(r"= %s\S* (copy|transpose)\(" % re.escape("bf16[%d,%d,%d]" % cache), text)
    wide = -(-P // T) * T
    assert f"f32[{B},{T},{wide}]" in text and f"u32[{B},{T},{wide}]" in text
    heads = pm._heads_a_step(T, lat, nope, rope, dv, H, 2, ds.KERNEL_HEADS, selects=True)
    assert heads == ds.KERNEL_HEADS and pm._step_bytes(T, lat, nope, rope, dv, heads, 2, selects=True) <= pm.VMEM_BUDGET
    assert pi._step_bytes(T, Hi, Di, 2) <= pi.VMEM_BUDGET


def test_the_delta_rule_kernel_compiles_for_v5e_with_no_chunk_tensor_in_hbm(one_chip):
    """Olmo-Hybrid-7B's gated delta rule of one prefill call at the cell's shapes (4 rows of 512 steps, 30 heads of
    96 | 192, chunks of 64), compiled by the TPU's compiler (nothing runs): one custom call and no loop; none of the
    chunked form's tensors a (row, chunk, head) in HBM (``K K^T``, the decays, ``T``: ``f32[4,8,30,64,64]``; ``W``,
    ``U'``); what XLA adds at the kernel's edges is q and k with each head's 96 columns padded to a lane tile and a
    few vectors of the size of g; a step's heads fit the budget by the module's own reckoning."""
    from daft_tpu.ops import pallas_delta_rule as pdr

    B, T, H, dk, dv, C = 4, 512, 30, 96, 192, 64
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, T, H, dk), jnp.bfloat16), ((B, T, H, dk), jnp.bfloat16), ((B, T, H, dv), jnp.bfloat16),
        ((B, T, H), jnp.float32), ((B, T, H), jnp.float32), ((B, H, dv, dk), jnp.float32))]
    compiled = jax.jit(lambda *a: pdr.gated_delta_fused(*a, chunk=C)).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and not re.search(r"= \S+ while\(", text)
    for gone in (f"f32[{B},{T // C},{H},{C},{C}]", f"f32[{B},{T // C},{H},{C},{dk}]", f"f32[{B},{T // C},{H},{C},{dv}]",
                 f"f32[{T // C},{B},{H},{C},{dv}]"):
        assert gone not in text, gone
    assert f"bf16[{B},{T},{H * 128}]" in text                                  # q and k as the kernel reads them
    assert compiled.memory_analysis().temp_size_in_bytes < 48 << 20           # two of those (15.7 MB each) and small change
    heads = pdr._heads_a_step(C, H, dk, dv)
    assert heads == pdr.MAX_HEADS and H % heads == 0 and pdr._step_bytes(C, dk, dv, heads) <= pdr.VMEM_BUDGET


def test_sdars_block_step_attention_compiles_for_v5e_with_no_copy_of_the_cache(one_chip, monkeypatch):
    """A pass's and the commit's attention of SDAR-30B-A3B-Chat's block step at the cell's sizes (64 slots of
    2,320 positions, 4 key/value heads of 128 with 8 queries each, blocks of 4), compiled by the TPU's compiler
    (nothing runs): one custom call, handed the caches with no copy of either, no float32 scores over every
    position a slot holds (XLA's ``[64,4,8,4,2324]``), and the commit's write in place."""
    from daft_tpu.models import sdar_moe as sd

    of = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    cfg = sd.SdarMoeConfig.from_name("SDAR-30B-A3B-Chat", num_layers=1)
    p = jax.tree_util.tree_map(lambda x: of(x.shape, x.dtype), jax.eval_shape(lambda: sd._init_layer(cfg, jax.random.PRNGKey(0))))
    cache = of((64, 4, 2320, 128), jnp.bfloat16)
    args = (p, of((64, 4, 2048), jnp.bfloat16), {"k": cache, "v": cache}, of((64,), jnp.int32), of((64,), jnp.bool_))
    rows = 64 * 4 * 2320 * 128 * 2
    for commit in (False, True):
        fn = jax.jit(lambda p, u, st, at, act: sd._attn_block(cfg, p, u, st, at, act, commit), donate_argnums=(2,))
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and "2324]" not in text
        assert not re.search(r"= bf16\[64,4,2320,128\]\S* (copy|transpose)\(", text)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < rows // 8 and (not commit or mem.alias_size_in_bytes >= 2 * rows)


@pytest.mark.parametrize("B,T,H,S", [(64, 2, 128, 2432), (16, 1, 64, 16449)], ids=["pangu_verify", "longcat_decode"])
def test_the_absorbed_core_compiles_for_v5e_with_no_score_tensor_and_no_copy_of_the_cache(one_chip, monkeypatch, B, T, H, S):
    """One attention of openPangu's speculative step (64 slots, two queries of 128 heads, 2,432 positions held) and
    of LongCat's decode step (16 slots, one query of 64 heads, 16,449 positions), as the models run it: the step's
    rows written (``latent_attention.write_token``), then ``core_absorbed`` with the backend rule answering as on a
    TPU. Compiled by the TPU's compiler (nothing runs): one custom call, the absorbed kernel's; no float32 scores over
    every position a slot holds (XLA's ``(B, H, T, S)``) and no copy of the cache."""
    from types import SimpleNamespace

    from daft_tpu.models import longcat_flash  # noqa: F401 (decoders first: it imports latent_attention)
    from daft_tpu.models import latent_attention as la
    from daft_tpu.ops import pallas_mla_attention as pm

    lat, rope, nope, dv = 512, 64, 128, 128
    cfg = SimpleNamespace(qk_nope_head_dim=nope, qk_rope_head_dim=rope, qk_head_dim=nope + rope, v_head_dim=dv,
                          kv_lora_rank=lat, dtype=jnp.bfloat16)
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)

    def step(q, kv, w_kvb, rows, positions, active):
        for t in range(T):
            kv = la.write_token(kv, rows[:, t], positions[:, t], active)
        return la.core_absorbed(cfg, w_kvb, q, kv, positions, 0.07, active=active), kv

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, T, H, nope + rope), jnp.bfloat16), ((B, lat + rope, S), jnp.bfloat16), ((lat, H, nope + dv), jnp.bfloat16),
        ((B, T, lat + rope), jnp.bfloat16), ((B, T), jnp.int32), ((B,), jnp.bool_))]
    text = jax.jit(step, donate_argnums=(1,)).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "mla_absorbed_attention" in text
    assert f"f32[{B},{H},{T},{S}]" not in text and f"bf16[{B},{H},{T},{S}]" not in text
    assert not re.search(r"= bf16\[%d,%d,%d\]\S* (copy|transpose)\(" % (B, lat + rope, S), text)
    assert pm.mla_absorbed_applies((B, T, H, lat + rope), jnp.bfloat16, S, lat)
