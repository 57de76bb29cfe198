"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692) over ONE token axis on which documents lie end to end.

Per head (a state ``S`` [dk, dv], zero before a document's first token;
``alpha_t = exp(g_t)`` a decay per key channel, ``beta_t`` in (0, 1)):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

A token-by-token loop is thousands of dependent steps a launch, so this is
the chunked form (the WY representation of the delta rule, arXiv:2412.06464,
with the decay kept per channel): the axis is cut into chunks of ``CHUNK``
tokens.  With ``G`` the running sum of ``g`` inside the chunk, ``S_0`` the
state that came in, ``A[t, j] = beta_t sum_c k_t[c] k_j[c] exp(G_t[c] -
G_j[c])`` for ``j < t`` and ``P[t, j]`` the same with ``q_t`` for ``j <= t``:

    U = (I + A)^-1 (beta V - beta (K * exp(G)) S_0)
    O = (Q * exp(G)) S_0 + P U
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

The decay inside a chunk is never factored as ``exp(G_t) exp(-G_j)``: a
head that forgets 11 nats a token (the published ``A_log`` reaches that)
overflows float32 within eight tokens.  Instead the chunk is cut into
sub-blocks of ``SUB`` tokens:

* a pair inside one sub-block takes ``exp(G_t - G_j)`` pairwise, channel by
  channel (``SUB`` rounds a chunk, each over all rows at once);
* a pair across sub-blocks factors through the first position ``r`` of the
  query's sub-block, ``exp(G_t - G_r) exp(G_r - G_j)``: both exponents are
  at most 0, so neither factor overflows, and the product is one matrix
  product a sub-block.

``(I + A)^-1`` is taken with matrix products alone: ``A = D + O`` with ``D``
its sub-blocks on the diagonal; ``(I + D)^-1 = (I - D)(I + D^2)(I + D^4)
(I + D^8)`` (``D^SUB`` vanishes); ``M = (I + D)^-1 O`` is strictly lower by
sub-blocks, so ``(I + M)^-1 = (I - M)(I + M^2)`` for four of them; and
``(I + A)^-1 = (I + M)^-1 (I + D)^-1``.  The product form over the whole
chunk, ``(I - A)(I + A^2)...(I + A^32)``, would do with as many products,
but its partial products grow like binomials where keys are nearly parallel
(``C(63, 31)`` ~ 1e18 for equal keys at ``beta`` 1) and cancel in float32;
here a partial product stays within ``C(15, 7)``.

Documents are kept apart exactly, wherever their borders fall:

* ``A`` and ``P`` take pair ``(t, j)`` only where ``seg`` agrees, so ``(I +
  A)`` is block diagonal by document and so is its inverse;
* the carried state reaches a token only if its document began before the
  chunk (``pos`` larger than the token's offset in the chunk);
* the state handed on is that of the chunk's LAST document alone, and what
  came in survives only if that document began before the chunk;
* a padding token (``valid`` false) has ``beta`` 0 and ``g`` 0: it writes
  nothing and decays nothing.  Its own output is read by nobody.

Everything here is float32, products at ``Precision.HIGHEST`` (a float32
product at the default precision is a bfloat16 one on a TPU).

Two implementations of the chunk loop over one preparation
(:func:`_operands`) and one chunk step (:func:`_chunk`), chosen by platform
as the repo's other kernels are: on a TPU the Pallas kernel ``pw_kda_scan``
(grid over groups of heads and chunks, the chunks walked in order with the
heads' states in VMEM scratch), elsewhere plain XLA (a ``lax.scan`` over the
chunks, the heads batched), which is also what the kernel is tested against.
Neither holds a [chunks, heads, C, C, dk] tensor: the pairwise decay lives
one sub-block offset at a time, [C, dk] a head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_scan", "kda_scan_xla", "kda_scan_pallas", "CHUNK", "SUB", "HEADS_PER_STEP",
           "KERNEL_NAME"]

#: the kernel's name in a device trace
KERNEL_NAME = "pw_kda_scan"
#: tokens a chunk, and a sub-block of it
CHUNK = 64
SUB = 16
#: heads a step of the kernel's grid walks (one where the heads do not divide)
HEADS_PER_STEP = 4

_HIGHEST = jax.lax.Precision.HIGHEST
#: columns of the per-token operand: seg, reach, tail; padded to eight
_META = 8


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """``a @ b.T``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _operands(q, k, v, g, beta, seg, pos, valid):
    """What both implementations read, over ``nc`` whole chunks: ``q``,
    ``k``, ``v`` [nc * C, H, d] float32, ``G`` [nc * C, H, dk] the running sum
    of ``g`` inside each chunk, ``beta`` [nc * C, H] (both 0 on padding),
    ``meta`` [nc * C, 8]: seg, reach (the carried state reaches the token),
    tail (the token is of its chunk's last document)."""
    t, chunk = q.shape[0], CHUNK
    if seg is None:
        seg = jnp.zeros((t,), jnp.int32)
    if valid is None:
        valid = jnp.ones((t,), bool)
    nc = -(-t // chunk)
    behind = nc * chunk - t
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = (f32(a) for a in (q, k, v, g, beta))
    pos, seg = pos.astype(jnp.int32), seg.astype(jnp.int32)
    if behind:  # whole chunks: what is added is padding of a document of its own
        pad = lambda a, value=0: jnp.pad(
            a, ((0, behind),) + ((0, 0),) * (a.ndim - 1), constant_values=value)
        q, k, v, g, beta, pos, valid = (pad(a) for a in (q, k, v, g, beta, pos, valid))
        seg = pad(seg, -1)
    g = jnp.where(valid[:, None, None], g, 0.0)
    beta = jnp.where(valid[:, None], beta, 0.0)
    cut = lambda a: a.reshape((nc, chunk) + a.shape[1:])
    G = jnp.cumsum(cut(g), axis=1).reshape(g.shape)
    reach = valid & (pos > jnp.arange(nc * chunk) % chunk)
    tail = cut(seg) == cut(seg)[:, -1:]
    meta = jnp.stack([f32(seg), f32(reach), f32(tail.reshape(-1))], axis=1)
    meta = jnp.pad(meta, ((0, 0), (0, _META - meta.shape[1])))
    return {"q": q, "k": k, "v": v, "G": G, "beta": beta, "meta": meta, "nc": nc}


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` [C, C] by matrix
    products; see the module."""
    c, sub = a.shape[0], SUB
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = jnp.where(ii == jj, 1.0, 0.0)
    in_block = jnp.bitwise_and(ii, -sub) == jnp.bitwise_and(jj, -sub)

    def neumann(m, order: int):  # (I + m)^-1 where m^order vanishes
        inv, power, n = eye - m, m, 2
        while n < order:
            power = _dot(power, power)
            inv = _dot(inv, eye + power)
            n *= 2
        return inv

    diag = neumann(jnp.where(in_block, a, 0.0), sub)
    return _dot(neumann(_dot(diag, jnp.where(in_block, 0.0, a)), c // sub), diag)


def _chunk(q, k, v, G, beta, seg, reach, tail, state, rows_at):
    """One head over one chunk.  ``q``, ``k``, ``G`` [C, dk], ``v`` [C, dv];
    ``beta``, ``seg``, ``reach``, ``tail`` columns [C, 1]; ``state`` the
    carried ``S^T`` [dv, dk].  ``rows_at(name, o)`` is [C, dk]: each
    sub-block's row ``o`` of ``k`` or ``G`` over all its rows.  Returns (``o``
    [C, dv], the state handed on)."""
    c, sub = q.shape[0], SUB
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    start = jnp.bitwise_and(ii, -sub)  # the first row of the query's sub-block
    seg_row = jnp.sum(jnp.where(ii == jj, seg, 0.0), axis=0, keepdims=True)
    same = seg == seg_row
    # inside a sub-block: exp(G_t - G_j) pairwise, key offset o at a time
    qk = kk = jnp.zeros((c, c), jnp.float32)
    for o in range(sub):
        f = rows_at("k", o) * jnp.exp(jnp.minimum(G - rows_at("G", o), 0.0))
        at = jj == start + o
        qk = jnp.where(at, jnp.sum(q * f, axis=1, keepdims=True), qk)
        kk = jnp.where(at, jnp.sum(k * f, axis=1, keepdims=True), kk)
    # across sub-blocks: through the first position r of the query's
    # sub-block, exp(G_t - G_r) exp(G_r - G_j), both factors at most 1
    early_q = [jnp.zeros((sub, c), jnp.float32)]
    early_k = [jnp.zeros((sub, c), jnp.float32)]
    for r in range(sub, c, sub):
        g_r = G[r:r + 1]
        back = jnp.exp(G[r:r + sub] - g_r)
        keys = k * jnp.exp(jnp.minimum(g_r - G, 0.0))
        early_q.append(_dot_t(q[r:r + sub] * back, keys))
        early_k.append(_dot_t(k[r:r + sub] * back, keys))
    before = jj < start
    qk = jnp.where(before, jnp.concatenate(early_q, axis=0), qk)
    kk = jnp.where(before, jnp.concatenate(early_k, axis=0), kk)
    p = jnp.where(same & (jj <= ii), qk, 0.0)
    inv = _unit_lower_inverse(jnp.where(same & (jj < ii), kk, 0.0) * beta)
    decay = jnp.exp(G)  # from the chunk's start: at most 1
    w_v = _dot(inv, v * beta)
    w_k = _dot(inv, k * (beta * reach) * decay)
    u = w_v - _dot_t(w_k, state)
    o = _dot_t(q * reach * decay, state) + _dot(p, u)
    last = G[c - 1:c]
    new = (state * (jnp.exp(last) * reach[c - 1:c])
           + _dot(u.T, k * tail * jnp.exp(last - G)))
    return o, new


def _finish(o, t: int, h: int):
    return o.reshape(o.shape[0], h, -1)[:t]


def kda_scan_xla(q, k, v, g, beta, seg, pos, valid):
    """The chunk loop as a ``lax.scan`` over chunks, heads batched; see the
    module."""
    t, h, dk = q.shape
    o = _operands(q, k, v, g, beta, seg, pos, valid)
    nc, c, sub = o["nc"], CHUNK, SUB
    heads = lambda a: a.reshape(nc, c, h, -1).transpose(0, 2, 1, 3)  # [nc, H, C, d]
    xs = (heads(o["q"]), heads(o["k"]), heads(o["v"]), heads(o["G"]),
          o["beta"].reshape(nc, c, h).transpose(0, 2, 1)[..., None],
          o["meta"].reshape(nc, c, _META))

    def one_head(q_, k_, v_, G_, beta_, meta, state):
        arrays = {"k": k_, "G": G_}

        def rows_at(name, off):
            a = arrays[name].reshape(c // sub, sub, dk)[:, off:off + 1]
            return jnp.broadcast_to(a, (c // sub, sub, dk)).reshape(c, dk)

        return _chunk(q_, k_, v_, G_, beta_, meta[:, 0:1], meta[:, 1:2], meta[:, 2:3],
                      state, rows_at)

    heads_step = jax.vmap(one_head, in_axes=(0, 0, 0, 0, 0, None, 0))

    def step(state, x):
        out, new = heads_step(*x, state)
        return new, out

    state0 = jnp.zeros((h, v.shape[-1], dk), jnp.float32)
    _, out = jax.lax.scan(step, state0, xs)  # [nc, H, C, dv]
    return _finish(out.transpose(0, 2, 1, 3).reshape(nc * c, -1), t, h)


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, meta_ref, o_ref, state_ref, *,
            heads: int, dk: int, dv: int):
    """One (head group, chunk) step: the group's ``heads`` heads over one
    chunk, their states [heads, dv, dk] in ``state_ref`` from the chunk
    before."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    c, sub = q_ref.shape[0], SUB
    seg, reach, tail = meta_ref[:, 0:1], meta_ref[:, 1:2], meta_ref[:, 2:3]
    for head in range(heads):
        kc, vc = slice(head * dk, (head + 1) * dk), slice(head * dv, (head + 1) * dv)
        refs = {"k": k_ref, "G": g_ref}

        def rows_at(name, off, kc=kc):
            ref = refs[name]
            return jnp.concatenate(
                [jnp.broadcast_to(ref[r + off:r + off + 1, kc], (sub, dk))
                 for r in range(0, c, sub)], axis=0)

        o, new = _chunk(
            q_ref[:, kc], k_ref[:, kc], v_ref[:, vc], g_ref[:, kc], beta_ref[:, head:head + 1],
            seg, reach, tail, state_ref[head], rows_at)
        o_ref[:, vc] = o
        state_ref[head] = new


def kda_scan_pallas(q, k, v, g, beta, seg, pos, valid, *, interpret: bool = False):
    """The chunk loop as the Pallas kernel ``pw_kda_scan``; see the module."""
    t, h, dk = q.shape
    dv, chunk = v.shape[-1], CHUNK
    hb = HEADS_PER_STEP if h % HEADS_PER_STEP == 0 else 1
    o = _operands(q, k, v, g, beta, seg, pos, valid)
    nc = o["nc"]
    flat = lambda a: a.reshape(nc * chunk, -1)
    beta_g = o["beta"].reshape(nc * chunk, h // hb, hb).transpose(1, 0, 2)
    rows = lambda width: pl.BlockSpec((chunk, hb * width), lambda gi, ci: (ci, gi))
    out = pl.pallas_call(
        functools.partial(_kernel, heads=hb, dk=dk, dv=dv),
        grid=(h // hb, nc),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk),
                  pl.BlockSpec((None, chunk, hb), lambda gi, ci: (gi, ci, 0)),
                  pl.BlockSpec((chunk, _META), lambda gi, ci: (ci, 0))],
        out_specs=rows(dv),
        out_shape=jax.ShapeDtypeStruct((nc * chunk, h * dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat(o["q"]), flat(o["k"]), flat(o["v"]), flat(o["G"]), beta_g, o["meta"])
    return _finish(out, t, h)


def kda_scan(q, k, v, g, beta, seg, pos, valid):
    """``o`` [T, H, dv] float32 of the recurrence above over one token axis.

    ``q``, ``k`` [T, H, dk] (normed and scaled by the caller), ``v`` [T, H,
    dv], ``g`` [T, H, dk] the log-decay (at most 0), ``beta`` [T, H];
    ``seg`` [T] the document of each token and ``valid`` [T] whether it is
    one (both None: one document, every token real), ``pos`` [T] positions
    in the document.  The Pallas kernel on a TPU, the XLA form elsewhere."""
    if jax.default_backend() == "tpu":
        return kda_scan_pallas(q, k, v, g, beta, seg, pos, valid)
    return kda_scan_xla(q, k, v, g, beta, seg, pos, valid)
