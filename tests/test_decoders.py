"""What every decoder shares in ``models/decoders.py``: the gated MLP, whose
activation ``silu(a) * b`` is written once in bfloat16 between its two products.
On the CPU it computes what the unbarriered form computes, bit for bit; compiled
for a described v5e (nothing runs), no product recomputes the gate inside it.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu.models import decoders, deepseek_v32 as ds, granite_hybrid as gh, longcat_flash as lc, olmo_hybrid as oh


def unbarriered(x, w_in, w_out, dtype):
    a, b = jnp.split(decoders.mm(x, w_in), 2, axis=-1)
    return decoders.mm((jax.nn.silu(a) * b).astype(dtype), w_out)


#: (hidden, FFN width) of every caller of ``gated_mlp`` at the tiny sizes.
TINY_WIDTHS = {
    "longcat_ffn": (lc.SIZES["longcat-flash-tiny"]["hidden_size"], lc.SIZES["longcat-flash-tiny"]["ffn_hidden_size"]),
    "deepseek_dense": (ds.SIZES["deepseek-v32-tiny"]["hidden_size"], ds.SIZES["deepseek-v32-tiny"]["intermediate_size"]),
    "deepseek_shared": (ds.SIZES["deepseek-v32-tiny"]["hidden_size"], ds.SIZES["deepseek-v32-tiny"]["moe_intermediate_size"]),
    "olmo_mlp": (oh.SIZES["olmo-hybrid-tiny"]["hidden_size"], oh.SIZES["olmo-hybrid-tiny"]["intermediate_size"]),
    "granite_shared": (gh.SIZES["granite-hybrid-tiny"]["hidden_size"], gh.SIZES["granite-hybrid-tiny"]["shared_intermediate_size"]),
}


@pytest.mark.parametrize("d,f", list(TINY_WIDTHS.values()), ids=list(TINY_WIDTHS))
def test_gated_mlp_equals_the_unbarriered_form_bit_for_bit(d, f):
    kx, ki, ko = jax.random.split(jax.random.PRNGKey(d + f), 3)
    x = jax.random.normal(kx, (3, 7, d), jnp.float32).astype(jnp.bfloat16)
    w_in = decoders.draw(ki, (d, 2 * f), "matrix").astype(jnp.bfloat16)
    w_out = decoders.draw(ko, (f, d), "matrix").astype(jnp.bfloat16)
    for run in (lambda fn: fn, jax.jit):
        got = run(lambda *a: decoders.gated_mlp(*a, jnp.bfloat16))(x, w_in, w_out)
        want = run(lambda *a: unbarriered(*a, jnp.bfloat16))(x, w_in, w_out)
        assert got.dtype == want.dtype == jnp.float32 and got.shape == (3, 7, d)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- compiled for the chip, without the chip ----------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no described v5e here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _computations(text: str) -> dict:
    """Optimised HLO text -> {computation name: its body}."""
    return {m.group(1): m.group(2) for m in re.finditer(r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)\n\}", text, re.M | re.S)}


def _reached(comps: dict, name: str) -> str:
    """A computation's body and those of every computation it calls, nested fusions included."""
    seen, todo, out = set(), [name], []
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen.add(n)
        out.append(comps[n])
        todo += re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", comps[n])
    return "\n".join(out)


@pytest.mark.parametrize("d,f", [(6144, 12288), (7168, 18432)], ids=["longcat_ffn", "deepseek_dense"])
def test_no_down_projection_recomputes_the_gate_for_a_described_v5e(one_chip, d, f):
    """One prefill call's dense path (4 x 512 tokens): the norm, ``gated_mlp``, the residual add and the next norm's
    statistics, which XLA fuses into the down projection's epilogue. No product's fusion computes the gate
    (``exponential`` / ``logistic``), and the down projection reads the activation as a bfloat16 parameter."""
    of = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731

    def layer(x, n0, w_in, w_out, n1):
        x = x + decoders.gated_mlp(decoders.rms(x, n0, 1e-5).astype(jnp.bfloat16), w_in, w_out, jnp.bfloat16)
        return x, decoders.rms(x, n1, 1e-5)

    text = jax.jit(layer).lower(of((4, 512, d), jnp.float32), of((d,), jnp.float32), of((d, 2 * f), jnp.bfloat16),
                                of((f, d), jnp.bfloat16), of((d,), jnp.float32)).compile().as_text()
    comps = _computations(text)
    products = [n for n, body in comps.items() if " convolution(" in body and not n.startswith("main")]
    assert len(products) == 2, products
    for n in products:
        ops = set(re.findall(r"= \S+ ([a-z\-]+)\(", _reached(comps, n)))
        assert not ops & {"exponential", "logistic"}, (n, ops)
    down = [n for n in products if f"bf16[{f},{d}]" in comps[n]]
    assert len(down) == 1
    params = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(", comps[down[0]])
    assert f"bf16[4,512,{f}]" in params, params
