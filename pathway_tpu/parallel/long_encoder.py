"""Sequence-parallel encoder forward: long documents over the mesh.

The reference can only chunk long inputs (splitters.py:34) because its
embedder is a single-device torch module.  Here the SAME checkpoint
params that drive :class:`pathway_tpu.models.encoder.TransformerEncoder`
run a sequence-parallel forward: token positions are sharded over the
mesh's sequence axis, attention is :func:`ring_attention` (kv blocks
rotate over ICI), every other sublayer is position-local, and the final
masked-mean pool is a ``psum`` — so one document's context can span
``n_devices × T_local`` tokens without any chip materializing the full
sequence.

This is a functional re-expression of the flax module (same param
pytree, same math: query-scaled attention, erf-GELU, post-LN residuals),
asserted equivalent to the single-device forward in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import ring_attention

__all__ = ["ring_encode", "ring_forward"]


def _layer_norm(x, p, eps):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _block(x, valid, p, axis_name, eps):
    """One encoder layer with ring attention (flax Block parity:
    models/encoder.py Block — attention → ln1 → mlp(erf gelu) → ln2)."""
    att = p["attention"]
    q = jnp.einsum("bth,hnd->btnd", x, att["query"]["kernel"]) + att["query"]["bias"]
    k = jnp.einsum("bth,hnd->btnd", x, att["key"]["kernel"]) + att["key"]["bias"]
    v = jnp.einsum("bth,hnd->btnd", x, att["value"]["kernel"]) + att["value"]["bias"]
    ctx = ring_attention(q, k, v, valid, axis_name)
    h = jnp.einsum("btnd,ndh->bth", ctx, att["out"]["kernel"]) + att["out"]["bias"]
    x = _layer_norm(x + h, p["ln1"], eps)
    h = jnp.einsum("bth,hm->btm", x, p["mlp_in"]["kernel"]) + p["mlp_in"]["bias"]
    h = jax.nn.gelu(h, approximate=False)
    h = jnp.einsum("btm,mh->bth", h, p["mlp_out"]["kernel"]) + p["mlp_out"]["bias"]
    return _layer_norm(x + h, p["ln2"], eps)


def ring_forward(params, ids, mask, *, num_layers: int, ln_eps: float,
                 axis_name: str, pool: bool = True):
    """Per-shard forward (call inside shard_map; seq axis sharded).

    ids/mask: ``[B, T_local]``; params: the TransformerEncoder pytree.
    """
    t_local = ids.shape[1]
    shard = lax.axis_index(axis_name)
    positions = shard * t_local + jnp.arange(t_local)[None, :]
    x = params["tok_emb"]["embedding"][ids]
    x = x + params["pos_emb"]["embedding"][positions]
    if "type_emb" in params:
        x = x + params["type_emb"]["embedding"][jnp.zeros_like(ids)]
    x = _layer_norm(x, params["ln_emb"], ln_eps)
    valid = mask.astype(bool)
    for i in range(num_layers):
        x = _block(x, valid, params[f"layer_{i}"], axis_name, ln_eps)
    if not pool:
        return x
    m = mask[:, :, None].astype(jnp.float32)
    num = lax.psum(jnp.sum(x * m, axis=1), axis_name)
    den = lax.psum(jnp.sum(m, axis=1), axis_name)
    pooled = num / jnp.maximum(den, 1e-9)
    if "proj" in params:
        pooled = (
            jnp.einsum("bh,he->be", pooled, params["proj"]["kernel"])
            + params["proj"]["bias"]
        )
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-12)


@functools.lru_cache(maxsize=None)
def _compiled(mesh: Mesh, axis: str, num_layers: int, ln_eps: float,
              pool: bool):
    fwd = functools.partial(
        ring_forward, num_layers=num_layers, ln_eps=ln_eps,
        axis_name=axis, pool=pool,
    )

    @jax.jit
    def run(params, ids, mask):
        out_spec = P() if pool else P(None, axis)
        f = jax.shard_map(
            fwd,
            mesh=mesh,
            in_specs=(P(), P(None, axis), P(None, axis)),
            out_specs=out_spec,
            check_vma=False,  # pooled output is replicated via psum
        )
        return f(params, ids, mask)

    return run


def ring_encode(params, ids, mask, mesh: Mesh, axis: str, *,
                num_layers: int, ln_eps: float = 1e-12,
                pool: bool = True):
    """Sequence-parallel encode of ``[B, T_global]`` token ids; T_global
    must divide evenly by the mesh's ``axis`` size."""
    n = mesh.shape[axis]
    if ids.shape[1] % n:
        raise ValueError(
            f"global sequence {ids.shape[1]} not divisible by mesh axis "
            f"{axis} size {n}"
        )
    max_len = params["pos_emb"]["embedding"].shape[0]
    if ids.shape[1] > max_len:
        # jit would silently clamp the position gather — wrong embeddings
        raise ValueError(
            f"global sequence {ids.shape[1]} exceeds the checkpoint's "
            f"position table ({max_len}); extend pos_emb before encoding"
        )
    seq_spec = NamedSharding(mesh, P(None, axis))
    ids = jax.device_put(jnp.asarray(ids, jnp.int32), seq_spec)
    mask = jax.device_put(jnp.asarray(mask, jnp.int32), seq_spec)
    params = jax.device_put(params, NamedSharding(mesh, P()))
    return _compiled(mesh, axis, num_layers, ln_eps, pool)(params, ids, mask)
