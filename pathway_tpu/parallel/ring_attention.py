"""Ring attention: sequence-parallel attention over a mesh axis.

The reference has no sequence parallelism at all (SURVEY §5 long-context:
its only long-input tool is document chunking, splitters.py:34).  The TPU
build makes long context first-class: documents longer than one chip's
comfortable sequence length are sharded over the mesh's sequence axis and
attended with the ring algorithm — each device holds one query block and
rotates key/value blocks around the ring with ``lax.ppermute`` (one ICI
hop per step), accumulating softmax online in the numerically-stable
flash style.  Peak memory per chip stays O(T_local²-ish) while the
effective context is T_local × ring_size; the collectives ride ICI.

Layout convention: ``[batch, seq_local, heads, head_dim]`` inside
``shard_map`` with the sequence axis sharded over ``axis_name``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_sharded"]

NEG_INF = -1e30


def ring_attention(q, k, v, kv_valid, axis_name: str):
    """Bidirectional (encoder) attention with the kv blocks ring-rotated.

    q, k, v: ``[B, T_local, H, Dh]`` — the sequence axis is sharded over
    ``axis_name``; kv_valid: ``[B, T_local]`` bool — padding mask for the
    local kv block.  Returns ``[B, T_local, H, Dh]`` in fp32.

    Online-softmax accumulation: running max ``m``, normalizer ``l`` and
    unnormalized output ``o`` are updated per ring step, so no step ever
    materializes the full [T, T_global] score matrix.
    """
    n = lax.psum(1, axis_name)
    b, t, h, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    qf = q.astype(jnp.float32)

    m = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    o = jnp.zeros((b, t, h, dh), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]
    for _step in range(n):
        s = jnp.einsum(
            "bthd,bshd->bhts", qf, k.astype(jnp.float32)
        ) * scale
        s = jnp.where(kv_valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows: exp(NEG_INF - NEG_INF) must not be 1
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(kv_valid[:, None, None, :], p, 0.0)
        corr = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - safe_m)
        corr = jnp.where(m <= NEG_INF / 2, 0.0, corr)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhts,bshd->bthd", p, v.astype(jnp.float32)
        )
        m = m_new
        if _step < n - 1:  # the last step's rotation would never be read
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
            kv_valid = lax.ppermute(kv_valid, axis_name, perm)
    l_t = l.transpose(0, 2, 1)[..., None]  # [B, T, H, 1]
    return o / jnp.maximum(l_t, 1e-30)


@functools.lru_cache(maxsize=None)
def _compiled_ring(mesh: Mesh, axis: str):
    # jit specializes on shapes/dtypes itself — cache only per (mesh, axis)

    @jax.jit
    def run(q, k, v, valid):
        f = jax.shard_map(
            lambda q, k, v, m: ring_attention(q, k, v, m, axis),
            mesh=mesh,
            in_specs=(P(None, axis), P(None, axis), P(None, axis), P(None, axis)),
            out_specs=P(None, axis),
        )
        return f(q, k, v, valid)

    return run


def ring_attention_sharded(q, k, v, kv_valid, mesh: Mesh, axis: str):
    """Host-facing helper: place global ``[B, T, H, Dh]`` arrays with the
    sequence axis sharded over ``axis`` and run ring attention."""
    spec = NamedSharding(mesh, P(None, axis))
    q, k, v = (jax.device_put(x, spec) for x in (q, k, v))
    kv_valid = jax.device_put(kv_valid, spec)
    return _compiled_ring(mesh, axis)(q, k, v, kv_valid)
