"""The grouped-matmul kernel (``ops/pallas_grouped_matmul.py``): its arithmetic in
interpret mode on the CPU against ``lax.ragged_dot`` on the rows the groups own,
the walk it is handed, the rule that selects it, and ``granite_hybrid._moe`` on
either path. Its compile for a described v5e at the benchmark's shapes is in
``tests/test_pallas.py``, with the fixture that describes the chip."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu.models import granite_hybrid as gh
from daft_tpu.ops import pallas_attention as pa
from daft_tpu.ops import pallas_grouped_matmul as gmm

M, K, N, G = 96, 128, 256, 6   # six groups over 96 rows: row tiles of 16

#: name -> sizes (G,). Row tiles are 16 rows: [3, 40, ...] has group 1 on rows 3..42, three tiles.
LAYOUTS = {
    "empty_first": [0, 20, 16, 9, 30, 5],
    "empty_last": [20, 16, 9, 30, 5, 0],
    "empty_in_the_middle": [7, 0, 0, 33, 0, 12],
    "crosses_three_tiles": [3, 40, 0, 0, 2, 1],
    "many_in_one_tile": [2, 3, 1, 4, 2, 1],
    "nothing_held": [0, 0, 0, 0, 0, 0],
    "every_row_held": [16, 16, 16, 16, 16, 16],
    "uneven_and_full": [1, 50, 0, 13, 31, 1],
}


def _operands(dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((M, K)), dtype)
    w = jnp.asarray(rng.standard_normal((G, K, N)) * K ** -0.5, dtype)
    return x, w


def _gate(ab):
    a, b = jnp.split(ab, 2, axis=-1)
    return jax.nn.silu(a) * b


# -- arithmetic ------------------------------------------------------------------
@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_matches_ragged_dot_on_the_rows_the_groups_own(layout, dtype, tol, gated):
    """Rows behind the groups are NaN on the input side: none is read into a held
    row, and what comes back there does not matter."""
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    owned = int(sizes.sum())
    x, w = _operands(dtype, seed=len(layout))
    ref = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    ref = np.asarray(_gate(ref) if gated else ref)[:owned]
    poisoned = x.at[owned:].set(jnp.nan)
    out = gmm.grouped_matmul(poisoned, w, sizes, gated=gated, interpret=True)
    assert out.shape == (M, N // 2 if gated else N) and out.dtype == dtype
    got = np.asarray(out, np.float32)[:owned]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, atol=tol, rtol=tol)


@pytest.mark.parametrize("layout", ["crosses_three_tiles", "empty_in_the_middle", "uneven_and_full"])
def test_a_tile_is_multiplied_in_parts_and_only_where_the_group_owns_rows(monkeypatch, layout):
    """Tiles of 16 rows in parts of 8 (as 256 in parts of 64 at the benchmark's
    shapes): a part whose rows all belong to other groups is skipped, the result
    on owned rows is the same."""
    monkeypatch.setattr(gmm, "ROW_PART", 8)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    owned = int(sizes.sum())
    x, w = _operands(jnp.float32, seed=3)
    kernel = jax.jit(gmm.grouped_matmul.__wrapped__, static_argnames=("gated", "interpret"))  # traced with the patch
    for gated in (False, True):
        ref = jax.lax.ragged_dot(x, w, sizes)
        ref = np.asarray(_gate(ref) if gated else ref)[:owned]
        out = kernel(x.at[owned:].set(jnp.nan), w, sizes, gated=gated, interpret=True)
        np.testing.assert_allclose(np.asarray(out)[:owned], ref, atol=2e-5, rtol=2e-5)


def test_an_empty_groups_weights_are_never_read():
    sizes = jnp.asarray(LAYOUTS["empty_in_the_middle"], jnp.int32)
    x, w = _operands(jnp.float32)
    ref = np.asarray(jax.lax.ragged_dot(x, w, sizes))[:52]
    w = w.at[jnp.asarray([1, 2, 4])].set(jnp.nan)
    out = np.asarray(gmm.grouped_matmul(x, w, sizes, interpret=True))[:52]
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_rows_do_not_mix_across_a_shared_tile():
    """Groups 0..5 share tile 0: changing one group's weights changes its rows alone."""
    sizes = jnp.asarray(LAYOUTS["many_in_one_tile"], jnp.int32)
    x, w = _operands(jnp.float32)
    a = np.asarray(gmm.grouped_matmul(x, w, sizes, interpret=True))[:13]
    b = np.asarray(gmm.grouped_matmul(x, w.at[3].add(1.0), sizes, interpret=True))[:13]
    changed = np.abs(a - b).max(axis=1) > 1e-6
    assert changed.tolist() == [False] * 6 + [True] * 4 + [False] * 3


# -- the walk ----------------------------------------------------------------------
@pytest.mark.parametrize("layout,visits", [
    ("empty_first", [(1, 0), (1, 1), (2, 1), (2, 2), (3, 2), (4, 2), (4, 3), (4, 4), (5, 4)]),
    ("crosses_three_tiles", [(0, 0), (1, 0), (1, 1), (1, 2), (4, 2), (5, 2)]),
    ("many_in_one_tile", [(g, 0) for g in range(6)]),
    ("nothing_held", []),
    ("every_row_held", [(g, g) for g in range(6)]),
])
def test_the_walk_visits_each_owned_tile_and_nothing_behind_the_last_group(layout, visits):
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    offsets, group, tile, count = gmm.group_visits(sizes, M // 16, 16)
    assert offsets.tolist() == np.concatenate([[0], np.cumsum(LAYOUTS[layout])]).tolist()
    assert group.shape == tile.shape == (M // 16 + G - 1,) and group.dtype == tile.dtype == jnp.int32
    count = int(count)
    assert list(zip(group[:count].tolist(), tile[:count].tolist())) == visits
    # whatever the arrays hold past the count stays inside the operands
    assert 0 <= int(group.min()) and int(group.max()) < G and 0 <= int(tile.min()) and int(tile.max()) < M // 16


def test_tiles_follow_from_the_shapes():
    # the prefill call of the benchmark's cell: 20,480 assignments over 36 held experts, bfloat16
    tm, tn = gmm._tiles(20480, 4096, 768, 36, 2, True)
    assert tm == gmm.MAX_ROW_TILE and 768 % tn == 0 and tn % 128 == 0
    assert gmm._step_bytes(tm, 4096, tn, 2, True) <= gmm.VMEM_BUDGET
    tm, tn = gmm._tiles(20480, 768, 4096, 36, 2, False)
    assert tm == gmm.MAX_ROW_TILE and 4096 % tn == 0 and tn % 128 == 0
    # its decode step: 320 rows, nine an expert -> one sublane tile of bfloat16
    assert gmm._tiles(320, 4096, 768, 36, 2, True)[0] == 16
    assert gmm._tiles(320, 4096, 768, 36, 4, True)[0] == 16
    assert gmm._tiles(8, 128, 128, 2, 4, False)[0] == 8       # never taller than the rows, rounded to a tile
    assert gmm._tiles(4096, 1 << 20, 128, 4, 2, False)[1] == 0  # one lane tile of such a k exceeds the budget


# -- the rule that selects it ------------------------------------------------------
PUBLISHED = [((20480, 4096), (36, 4096, 1536), True), ((20480, 768), (36, 768, 4096), False),
             ((320, 4096), (36, 4096, 1536), True), ((320, 768), (36, 768, 4096), False)]


def test_cpu_backend_takes_ragged_dot():
    assert not pa.backend_is_tpu()
    for x_shape, w_shape, gated in PUBLISHED:
        assert not gmm.grouped_matmul_applies(x_shape, w_shape, jnp.bfloat16, gated=gated)


def test_on_a_tpu_the_published_widths_take_the_kernel_and_narrow_ones_do_not(monkeypatch):
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    for x_shape, w_shape, gated in PUBLISHED:
        assert gmm.grouped_matmul_applies(x_shape, w_shape, jnp.bfloat16, gated=gated)
        assert gmm.grouped_matmul_applies(x_shape, w_shape, jnp.float32, gated=gated)
        assert not gmm.grouped_matmul_applies(x_shape, w_shape, jnp.float16, gated=gated)
    # granite-hybrid-tiny: hidden 64, experts of 12
    assert not gmm.grouped_matmul_applies((120, 64), (4, 64, 24), jnp.bfloat16, gated=True)
    assert not gmm.grouped_matmul_applies((120, 12), (4, 12, 64), jnp.bfloat16)
    assert not gmm.grouped_matmul_applies((120, 128), (4, 128, 128), jnp.bfloat16, gated=True)  # halves of 64
    assert gmm.grouped_matmul_applies((120, 128), (4, 128, 256), jnp.bfloat16, gated=True)
    assert not gmm.grouped_matmul_applies((4096, 1 << 20), (4, 1 << 20, 128), jnp.bfloat16)    # beyond the budget


# -- granite_hybrid._moe on either path ---------------------------------------------
def _as_on_a_tpu(monkeypatch):
    """The backend rule answers as on a TPU, and the kernel it then selects runs
    interpreted. Returns the list that collects (x shape, gated) of the kernel's calls."""
    calls = []
    real = gmm.grouped_matmul

    def interpreted(x, w, sizes, gated=False):
        calls.append((x.shape, gated))
        return real(x, w, sizes, gated=gated, interpret=True)

    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(gmm, "grouped_matmul", interpreted)
    return calls


@pytest.fixture
def on_tpu(monkeypatch):
    return _as_on_a_tpu(monkeypatch)


def _expert_layer(d=128, f=128, seed=0):
    """One expert layer at a lane tile of width: rank 0 of two shares of 8 experts, top 3."""
    cfg = dataclasses.replace(gh.GraniteHybridConfig.from_name("granite-hybrid-tiny", expert_shard=(0, 2)),
                              hidden_size=d, intermediate_size=f, shared_intermediate_size=f)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    draw = lambda *shape: (jax.random.normal(next(keys), shape, jnp.float32) * shape[-2] ** -0.5).astype(cfg.dtype)  # noqa: E731
    p = {"router": draw(d, cfg.num_local_experts), "w_in": draw(cfg.held_experts, d, 2 * f),
         "w_out": draw(cfg.held_experts, f, d), "shared_in": draw(d, 2 * f), "shared_out": draw(f, d)}
    return cfg, p


@pytest.mark.parametrize("n,valid_rows", [(40, 40), (40, 29), (8, 0)], ids=["all_valid", "padded", "nothing_valid"])
def test_moe_with_the_kernel_equals_moe_on_xlas_path(monkeypatch, n, valid_rows):
    cfg, p = _expert_layer()
    v = jnp.asarray(np.random.default_rng(n + valid_rows).standard_normal((n, cfg.hidden_size)), cfg.dtype)
    valid = jnp.arange(n) < valid_rows
    y_xla, counts_xla = jax.jit(lambda p, v, ok: gh._moe(cfg, p, v, ok))(p, v, valid)
    calls = _as_on_a_tpu(monkeypatch)
    y, counts = jax.jit(lambda p, v, ok: gh._moe(cfg, p, v, ok))(p, v, valid)
    assert calls == [((n * 3, 128), True), ((n * 3, 128), False)]
    assert y.dtype == y_xla.dtype == jnp.float32 and np.isfinite(np.asarray(y)).all()
    scale = float(jnp.max(jnp.abs(y_xla)))
    assert float(jnp.max(jnp.abs(y - y_xla))) <= 2e-2 * scale  # bfloat16 products, two orders of summation
    assert {k: int(c) for k, c in counts.items()} == {k: int(c) for k, c in counts_xla.items()}
    assert int(counts["assignments"]) == valid_rows * 3


def test_narrow_experts_take_xla_on_a_tpu(on_tpu):
    """granite-hybrid-tiny as tier-1 and ``chip_smoke.py`` run it."""
    cfg = gh.GraniteHybridConfig.from_name("granite-hybrid-tiny", expert_shard=(0, 2))
    p = gh._init_layer(cfg, jax.random.PRNGKey(0), "attention")
    v = jnp.ones((8, cfg.hidden_size), cfg.dtype)
    y, _ = gh._moe(cfg, p, v, jnp.ones((8,), bool))
    assert on_tpu == [] and np.isfinite(np.asarray(y)).all()


def test_kernel_failure_propagates(monkeypatch):
    cfg, p = _expert_layer()

    def broken_kernel(x, w, sizes, gated=False):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(gmm, "grouped_matmul", broken_kernel)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        jax.jit(lambda p, v: gh._moe(cfg, p, v, jnp.ones((8,), bool)))(p, jnp.ones((8, 128), cfg.dtype))
