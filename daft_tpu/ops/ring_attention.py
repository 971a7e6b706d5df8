"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context sequences shard along the sequence dimension across chips; K/V
blocks rotate around the ring via `lax.ppermute` (one ICI hop per step)
while each chip accumulates its queries' attention with an online
(flash-style) softmax — max/denominator carried across blocks, so the
result is EXACT full attention with per-chip memory O(T/n · T/n) instead of
O(T²). (No reference analogue: the reference has no sequence/context
parallelism anywhere — SURVEY.md §"does not exist in the reference". This
is the TPU-native design: mesh axis + collective, not NCCL point-to-point.)

Usage under shard_map over a mesh with an "sp" axis:

    attn = shard_map(
        functools.partial(ring_attention, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None), P(None, "sp", None), P(None, "sp", None)),
        out_specs=P(None, "sp", None),
    )
    out = attn(q, k, v)   # q,k,v: [B, T, D] globally, T sharded over sp
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, scale: float | None = None) -> jax.Array:
    """Exact (non-causal) attention with K/V ring rotation.

    Args (per-chip shards under shard_map):
      q, k, v: [B, T_local, D]
      axis_name: the sequence-parallel mesh axis.
    Returns: [B, T_local, D] — this chip's query rows, attended over the
    FULL global sequence.
    """
    n = lax.psum(1, axis_name)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    qf = q.astype(jnp.float32) * scale

    # Initial accumulators derive from qf so they carry the same varying
    # manual axes as the loop outputs (shard_map tracks axis-variance; fresh
    # zeros would be "unvarying" and fail the scan carry check).
    m0 = qf.sum(axis=-1) * 0.0 - jnp.inf
    l0 = qf.sum(axis=-1) * 0.0
    o0 = qf * 0.0
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        k_cur, v_cur, m, l, o = carry
        s = jnp.einsum("btd,bsd->bts", qf, k_cur.astype(jnp.float32))
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bts,bsd->btd", p, v_cur.astype(jnp.float32))
        # Rotate the K/V block one hop around the ring; after n steps every
        # chip has seen every block. XLA overlaps the ppermute with the next
        # step's compute on real ICI.
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, m_new, l, o), None

    (_, _, _, l, o), _ = lax.scan(step, (k, v, m0, l0, o0), None, length=n)
    return (o / l[..., None]).astype(q.dtype)


def sequence_parallel_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                mesh, axis: str = "sp") -> jax.Array:
    """Convenience wrapper: shard [B, T, D] arrays over ``axis`` and run
    ring attention; returns the globally-assembled [B, T, D] result."""
    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis, None)
    fn = shard_map(functools.partial(ring_attention, axis_name=axis),
                   mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
