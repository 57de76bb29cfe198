"""The selective state-space recurrence of a Mamba-2 mixer over ONE token
axis on which documents lie end to end, and the causal depthwise convolution
that feeds it.

Per head ``h`` (``P`` channels, a state ``S`` [P, N]; ``B``/``C`` are shared
by the heads of a group, head ``h`` reads group ``h // (H / G)``):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      y_t = S_t C_t + D x_t

with ``S`` zero before a document's first token.  A token-by-token loop is
thousands of dependent steps a launch, so this is the chunked form (Mamba-2's
SSD, arXiv:2405.21060): the axis is cut into chunks of ``chunk`` tokens;
inside a chunk token ``i`` takes from token ``j <= i``
``exp(cs_i - cs_j) (C_i . B_j) dt_j x_j`` (``cs`` the running sum of
``dt A`` inside the chunk: products on the MXU), and one state a head is
carried from chunk to chunk.

Documents are kept apart exactly, wherever their borders fall:

* inside a chunk a token takes from token ``j`` only where ``seg`` agrees;
* the carried state reaches a token only if its document began before the
  chunk (``pos`` is larger than the token's offset in the chunk), which is
  to say it belongs to the document of the previous chunk's last token;
* the state handed on is that of the chunk's LAST document alone (tokens
  whose ``seg`` is the last token's), and what came in survives only if
  that document began before the chunk;
* a padding token (``valid`` false) has ``dt`` 0: it adds nothing and
  decays nothing.  Its own ``y`` is ``D x`` and is read by nobody.

Everything here is float32: decays, their sums and exponentials, the
products with ``B`` and ``C`` (``Precision.HIGHEST``: a float32 product at
the default precision is a bfloat16 one on a TPU), the state, ``D x``.  The
running sum inside a chunk is a plain ``cumsum``, so ``exp(cs_i - cs_j)``
carries the rounding of ``cs`` (2^-24 of up to a few hundred for a head that
forgets within a token or two): 1e-5 of such a head's output at worst.

Two implementations of the chunk loop over one preparation
(:func:`_chunk_operands`), chosen by platform as the repo's other kernels
are: on a TPU the Pallas kernel ``pw_ssd_scan`` (grid over groups and
chunks, the chunks walked in order with the group's states in VMEM scratch,
segment ids and log-decays as row operands, ``C B^T`` computed once a group),
elsewhere plain XLA (batched products over chunks, a ``lax.scan`` over the
carried states), which is also what the kernel is tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["causal_conv", "ssd_scan", "ssd_scan_xla", "ssd_scan_pallas", "KERNEL_NAME"]

#: the kernel's name in a device trace
KERNEL_NAME = "pw_ssd_scan"

_HIGHEST = jax.lax.Precision.HIGHEST
_MASKED = -1e30  # exponent of a pair that may not see each other
#: rows of the kernel's per-token operand: cs, reach * exp(cs), tail *
#: exp(cs_last - cs), seg; padded to a float32 tile's eight sublanes
_ROWS = 8


def causal_conv(x, weight, bias, pos):
    """Causal depthwise convolution along the token axis: ``x`` [T, C],
    ``weight`` [K, C] (tap ``k`` multiplies ``x[t - (K - 1 - k)]``, as a
    ``conv1d`` padded on the left by ``K - 1`` holds them), ``bias`` [C] or
    None (no bias), ``pos`` [T] each token's position in its document.  A
    tap that would read a token of the document before (``pos`` smaller
    than the tap's reach) is dropped, so documents packed end to end
    convolve as each does alone.  float32."""
    x = x.astype(jnp.float32)
    taps = weight.shape[0]
    out = (jnp.zeros_like(x) if bias is None
           else jnp.broadcast_to(bias.astype(jnp.float32), x.shape))
    for back in range(taps):
        shifted = x if back == 0 else jnp.pad(x, ((back, 0), (0, 0)))[: x.shape[0]]
        shifted = jnp.where((pos >= back)[:, None], shifted, 0.0)
        out = out + shifted * weight[taps - 1 - back].astype(jnp.float32)
    return out


def _chunk_operands(x, dt, A, B, C, seg, pos, valid, chunk: int):
    """What both implementations read, cut into ``nc`` chunks of ``Q``
    tokens: ``xdt`` [nc, Q, H, P] (``dt x``), ``B``/``C`` [nc, Q, G, N],
    ``cs`` [nc, Q, H] the running sum of ``dt A`` inside the chunk, ``seg``
    [nc, Q], ``reach`` [nc, Q] (the carried state reaches the token),
    ``tail`` [nc, Q] (the token is of the chunk's last document), ``keep``
    [nc] (what came in survives the chunk)."""
    t = x.shape[0]
    if seg is None:
        seg = jnp.zeros((t,), jnp.int32)
    if valid is None:
        valid = jnp.ones((t,), bool)
    nc = -(-t // chunk)
    behind = nc * chunk - t
    if behind:  # whole chunks: what is added is padding of a document of its own
        pad = lambda a, value=0: jnp.pad(
            a, ((0, behind),) + ((0, 0),) * (a.ndim - 1), constant_values=value)
        x, dt, B, C, pos, valid = (pad(a) for a in (x, dt, B, C, pos, valid))
        seg = pad(seg.astype(jnp.int32), -1)
    f32 = lambda a: a.astype(jnp.float32)
    dt = jnp.where(valid[:, None], f32(dt), 0.0)
    cut = lambda a: a.reshape((nc, chunk) + a.shape[1:])
    cs = jnp.cumsum(cut(dt * f32(A)[None, :]), axis=1)
    seg = cut(seg.astype(jnp.int32))
    reach = cut(valid & (pos.astype(jnp.int32) > jnp.arange(nc * chunk) % chunk))
    return {
        "xdt": cut(f32(x) * dt[:, :, None]), "B": cut(f32(B)), "C": cut(f32(C)),
        "cs": cs, "seg": seg, "reach": reach, "tail": seg == seg[:, -1:],
        "keep": reach[:, -1],
    }


def _finish(y, x, D, t: int):
    """[nc, Q, H, P] -> [T, H, P] with the skip ``D x``."""
    y = y.reshape((-1,) + y.shape[2:])[:t]
    return y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, :, None]


def ssd_scan_xla(x, dt, A, B, C, D, seg, pos, valid, *, chunk: int):
    """The chunked form as batched products over chunks; see the module.
    Heads lead and tokens trail in every intermediate ([nc, G, H/G, Q, ...]),
    so that each product is a batch of plain matrices."""
    t, h, p = x.shape
    g = B.shape[1]
    o = _chunk_operands(x, dt, A, B, C, seg, pos, valid, chunk)
    nc, q = o["seg"].shape
    cs = o["cs"].reshape(nc, q, g, h // g).transpose(0, 2, 3, 1)  # [nc, g, h, i]
    xdt = o["xdt"].reshape(nc, q, g, h // g, p).transpose(0, 2, 3, 1, 4)  # [nc, g, h, j, p]
    b, c = o["B"].transpose(0, 2, 1, 3), o["C"].transpose(0, 2, 1, 3)  # [nc, g, i, n]
    per_token = lambda a: a[:, None, None, :]  # [nc, Q] beside [nc, g, h, Q]
    see = (o["seg"][:, :, None] == o["seg"][:, None, :]) & jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(see[:, None, None],
                              cs[..., :, None] - cs[..., None, :], _MASKED))
    cb = jnp.einsum("cgin,cgjn->cgij", c, b, precision=_HIGHEST)
    y = jnp.einsum("cghij,cghjp->cghip", decay * cb[:, :, None], xdt, precision=_HIGHEST)
    # each chunk's own state at its end, of its last document alone
    w = jnp.where(per_token(o["tail"]), jnp.exp(cs[..., -1:] - cs), 0.0)
    local = jnp.einsum("cghjp,cgjn->cghpn", xdt * w[..., None], b, precision=_HIGHEST)
    survive = jnp.where(o["keep"][:, None, None], jnp.exp(cs[..., -1]), 0.0)  # [nc, g, h]

    def carry(state, chunk_):
        local_c, survive_c = chunk_
        return survive_c[..., None, None] * state + local_c, state

    _, came_in = jax.lax.scan(carry, jnp.zeros_like(local[0]), (local, survive))
    r = jnp.where(per_token(o["reach"]), jnp.exp(cs), 0.0)
    y = y + r[..., None] * jnp.einsum("cgin,cghpn->cghip", c, came_in, precision=_HIGHEST)
    return _finish(y.transpose(0, 3, 1, 2, 4).reshape(nc, q, h, p), x, D, t)


def _scan_kernel(survive_ref, rows_ref, x_ref, b_ref, c_ref, y_ref, state_ref, *,
                 heads: int, p: int, nc: int):
    """One (group, chunk) step: the group's ``heads`` heads over a chunk of
    Q tokens, their states [heads, P, N] in ``state_ref`` from the chunk
    before.  ``rows_ref`` [heads, 8, Q]: per token along the lanes, cs,
    reach * exp(cs), tail * exp(cs_last - cs), seg."""
    group, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = x_ref.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # a row vector [1, Q] as a column [Q, 1]: one entry a row survives the sum
    column = lambda row: jnp.sum(jnp.where(ii == jj, row, 0.0), axis=1, keepdims=True)
    b, cc = b_ref[...], c_ref[...]
    cb = jax.lax.dot_general(cc, b, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # [i, j]
    seg = rows_ref[0, 3:4, :]
    see = (jj <= ii) & (column(seg) == seg)
    for head in range(heads):
        rows = rows_ref[head]
        cs, reach, tail = rows[0:1], rows[1:2], rows[2:3]
        x = x_ref[:, head * p:(head + 1) * p]
        state = state_ref[head]
        decay = jnp.exp(jnp.where(see, column(cs) - cs, _MASKED))
        y = jnp.dot(decay * cb, x, precision=_HIGHEST, preferred_element_type=jnp.float32)
        y += column(reach) * jax.lax.dot_general(
            cc, state, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        y_ref[:, head * p:(head + 1) * p] = y
        survive = survive_ref[(group * heads + head) * nc + c]
        state_ref[head] = survive * state + jnp.dot(
            (x * column(tail)).T, b, precision=_HIGHEST, preferred_element_type=jnp.float32)


def ssd_scan_pallas(x, dt, A, B, C, D, seg, pos, valid, *, chunk: int,
                    interpret: bool = False):
    """The chunk loop as the Pallas kernel ``pw_ssd_scan``; see the module."""
    t, h, p = x.shape
    g, n = B.shape[1], B.shape[2]
    o = _chunk_operands(x, dt, A, B, C, seg, pos, valid, chunk)
    nc, q = o["seg"].shape
    cs = o["cs"]
    per_token = jnp.stack([
        cs,
        jnp.where(o["reach"][:, :, None], jnp.exp(cs), 0.0),
        jnp.where(o["tail"][:, :, None], jnp.exp(cs[:, -1:] - cs), 0.0),
        jnp.broadcast_to(o["seg"].astype(jnp.float32)[:, :, None], cs.shape),
    ])  # [4, nc, Q, H]
    rows = jnp.pad(per_token.transpose(3, 0, 1, 2).reshape(h, 4, nc * q),
                   ((0, 0), (0, _ROWS - 4), (0, 0)))
    survive = jnp.where(o["keep"][None, :], jnp.exp(cs[:, -1]).T, 0.0).reshape(h * nc)
    heads = h // g
    y = pl.pallas_call(
        functools.partial(_scan_kernel, heads=heads, p=p, nc=nc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, nc),
            in_specs=[
                pl.BlockSpec((heads, _ROWS, q), lambda gi, ci, _s: (gi, 0, ci)),
                pl.BlockSpec((q, heads * p), lambda gi, ci, _s: (ci, gi)),
                pl.BlockSpec((q, n), lambda gi, ci, _s: (ci, gi)),
                pl.BlockSpec((q, n), lambda gi, ci, _s: (ci, gi)),
            ],
            out_specs=pl.BlockSpec((q, heads * p), lambda gi, ci, _s: (ci, gi)),
            scratch_shapes=[pltpu.VMEM((heads, p, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nc * q, h * p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(survive, rows, o["xdt"].reshape(nc * q, h * p), o["B"].reshape(nc * q, g * n),
      o["C"].reshape(nc * q, g * n))
    return _finish(y.reshape(nc, q, h, p), x, D, t)


def ssd_scan(x, dt, A, B, C, D, seg, pos, valid, *, chunk: int):
    """``y`` [T, H, P] float32 of the recurrence above over one token axis.

    ``x`` [T, H, P], ``dt`` [T, H] (after its softplus), ``A`` [H]
    (negative), ``B``/``C`` [T, G, N], ``D`` [H]; ``seg`` [T] the document of
    each token and ``valid`` [T] whether it is one (both None: one document,
    every token real), ``pos`` [T] positions in the document.  The Pallas
    kernel on a TPU, the XLA form elsewhere."""
    if jax.default_backend() == "tpu":
        return ssd_scan_pallas(x, dt, A, B, C, D, seg, pos, valid, chunk=chunk)
    return ssd_scan_xla(x, dt, A, B, C, D, seg, pos, valid, chunk=chunk)
