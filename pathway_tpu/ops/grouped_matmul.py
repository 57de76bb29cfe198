"""The routed experts' grouped matrix product: rows sorted by group, each
group's rows times its own matrix, ``out[r] = lhs[r] @ rhs[g(r)]``.

Two implementations, chosen by platform as the repo's other kernels are: on
a TPU the Pallas kernel ``pw_grouped_matmul``, elsewhere XLA's
:func:`jax.lax.ragged_dot` (:func:`grouped_matmul_xla`), which is also what
the kernel is tested against.

The kernel (:func:`grouped_matmul_pallas`):

* **Grid**: ``(n tiles, visits)``.  A visit is one (group, row tile) pair
  whose rows overlap, in row order, so a group's visits are consecutive and a
  row tile shared by two groups is visited once by each; the group, the row
  tile and the fetch bookkeeping of every visit, and each group's first row,
  reach the kernel by scalar prefetch (:func:`_visits`).
* **Each group's matrix is read from HBM once a call** (once an n tile): the
  contraction is whole, a group's ``[K, tn]`` block stays in fast memory for
  all its visits, and the next group's block is fetched into a second slot
  while this group computes (the first visit of a group starts it), so the
  fetch hides behind a whole group's products and not one visit's.
* **Tiling**, from the shapes alone (:func:`tiling`): ``tn`` the whole output
  width where the two slots fit the fast-memory budget, else its largest
  divisor in lanes of 128 that fits, so the rows are read once a product or
  a few times; row tiles of 128 (:data:`ROW_TILE`).
* **Skipped rows**: rows past the groups' total are in no visit.  The grid is
  as long as the visits that exist (a traced count), so the padding pairs of a
  launch cost neither reads nor products, and their output rows are not
  defined: the caller masks them.  Inside a visit, rows of another group are
  computed and not stored.
* **Fused epilogue** (``gated``): ``rhs`` is ``[G, K, 2F]`` with the gate's
  columns first; the kernel reads the gate's and the up projection's blocks of
  the same columns, accumulates both in float32 and writes
  ``silu(gate) * up`` in ``lhs``'s dtype, ``[M, F]``: the float32 ``[M, 2F]``
  product never reaches HBM.  Plain: ``[M, N]`` float32.

Operands are multiplied as they come (bfloat16 in the embedders) with float32
accumulation; the SiLU and the product are float32 and are rounded once, as
the XLA form rounds them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "grouped_matmul_xla", "grouped_matmul_pallas",
           "grouped_matmul_impl", "tiling", "ROW_TILE", "KERNEL_NAME"]

#: the kernel's name in a device trace
KERNEL_NAME = "pw_grouped_matmul"

#: rows a visit computes.  Rows of another group in a visit's tile are
#: computed and dropped, so a larger tile wastes more products on groups of
#: ~90-200 rows (the three embedders' launches); alone on a v5e both products
#: of a layer ran slower with 256 (+24%, +16%, +11%) and 512 at all three
#: embedders' shapes
ROW_TILE = 128
#: fast memory the two slots of a group's matrix may take, in bytes
_VMEM_BUDGET = 40 << 20
_LANES = 128


def grouped_matmul_impl() -> str:
    """``"pallas"`` on a TPU, ``"xla"`` elsewhere: what :func:`grouped_matmul`
    runs, read when a program is traced."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _epilogue(h, f: int, dtype):
    """``silu(h[:, :f]) * h[:, f:]`` in float32, rounded once to ``dtype``."""
    return (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(dtype)


def grouped_matmul_xla(lhs, rhs, group_sizes, *, gated: bool = False):
    """:func:`jax.lax.ragged_dot` with float32 accumulation (``ragged-dot`` in a
    device trace); ``gated`` as the module says."""
    h = jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)
    return _epilogue(h, rhs.shape[2] // 2, lhs.dtype) if gated else h


def tiling(k: int, n: int, *, gated: bool, itemsize: int = 2) -> int:
    """``tn``, the output columns of a visit, for matrices ``[k, n]``
    (``gated``: ``n`` is the gate's and up's columns together and ``tn``
    tiles each half); see the module."""
    width = n // 2 if gated else n
    halves = 2 if gated else 1
    fits = lambda tn: 2 * halves * k * tn * itemsize <= _VMEM_BUDGET
    if width % _LANES or fits(width):
        return width
    return next((lanes * _LANES for lanes in range(width // _LANES, 1, -1)
                 if width % (lanes * _LANES) == 0 and fits(lanes * _LANES)), _LANES)


def _visits(group_sizes, tm: int, tiles_m: int):
    """Scalar-prefetch operands: each group's first row ``[G + 1]`` (the last
    entry the groups' total), then for each visit (``tiles_m + G - 1``
    entries, the most there can be) its group, its row tile, whether it is
    its group's first, the next group that has rows (``G``: none) and the
    slot of fast memory its group's matrix is fetched into; and how many
    visits there are."""
    groups = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    visit_starts = visit_ends - tiles
    v = jnp.arange(tiles_m + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"), groups - 1)
    held = sizes > 0
    ahead = jax.lax.cummin(jnp.where(held, jnp.arange(groups), groups), reverse=True)
    following = jnp.concatenate([ahead[1:], jnp.full((1,), groups, ahead.dtype)])
    slot = (jnp.cumsum(held) - 1)[group] % 2
    per_visit = [group, first_tile[group] + v - visit_starts[group],
                 v == visit_starts[group], following[group], slot]
    offsets = jnp.concatenate([starts, ends[-1:]])
    return ([offsets] + [a.astype(jnp.int32) for a in per_visit]), visit_ends[-1]


def _kernel(offsets_ref, groups_ref, tiles_ref, first_ref, next_ref, slot_ref,
            lhs_ref, rhs_hbm, out_ref, w_ref, sem, *, tm: int, tn: int, columns: tuple):
    """One visit: its row tile times its group's matrix (``gated``: the
    gate's and up's blocks, ``columns`` their first columns over ``tn``
    tiles).  A group's matrix is fetched into slot ``slot`` of ``w_ref``
    while the group before it is computed: the first visit of each group
    waits for its own and starts the next group's into the other slot."""
    j, v = pl.program_id(0), pl.program_id(1)
    groups = rhs_hbm.shape[0]

    def fetch(group, slot):
        return [pltpu.make_async_copy(
            rhs_hbm.at[group, :, pl.ds(column + j * tn, tn)], w_ref.at[slot, half],
            sem.at[slot]) for half, column in enumerate(columns)]

    @pl.when(v == 0)
    def _():
        for copy in fetch(groups_ref[0], slot_ref[0]):
            copy.start()

    slot = slot_ref[v]

    @pl.when(first_ref[v] == 1)
    def _():
        for copy in fetch(groups_ref[v], slot):
            copy.wait()

        @pl.when(next_ref[v] < groups)
        def _():
            for copy in fetch(next_ref[v], 1 - slot):
                copy.start()

    x = lhs_ref[...]
    h = [jnp.dot(x, w_ref[slot, half], preferred_element_type=jnp.float32)
         for half in range(len(columns))]
    value = jax.nn.silu(h[0]) * h[1] if len(columns) == 2 else h[0]
    g = groups_ref[v]
    rows = tiles_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, value.shape, 0)
    mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
    out_ref[...] = jnp.where(mine, value.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("gated", "interpret"))
def grouped_matmul_pallas(lhs, rhs, group_sizes, *, gated: bool = False,
                          interpret: bool = False):
    """The Pallas kernel ``pw_grouped_matmul``; see the module.  ``lhs``
    [M, K] rows sorted by group, ``rhs`` [G, K, N], ``group_sizes`` [G].
    Jitted, so that a program whose layers share shapes traces and lowers
    the kernel once, not once a layer."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tn = ROW_TILE, tiling(k, n, gated=gated, itemsize=rhs.dtype.itemsize)
    width = n // 2 if gated else n
    columns = (0, width) if gated else (0,)
    out_dtype = lhs.dtype if gated else jnp.float32
    tiles_m = -(-m // tm)
    if tiles_m * tm != m:
        lhs = jnp.pad(lhs, ((0, tiles_m * tm - m), (0, 0)))
    prefetch, visits = _visits(group_sizes, tm, tiles_m)
    tile_of = lambda j, v, _o, _g, t, *_: t[v]
    held = (2 * len(columns) * k * tn * rhs.dtype.itemsize
            + 2 * (tm * k * lhs.dtype.itemsize + tm * tn * jnp.dtype(out_dtype).itemsize)
            + (len(columns) + 1) * tm * tn * 4)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, columns=columns),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            # at least one visit: a call whose groups are all empty stores nothing
            grid=(width // tn, jnp.maximum(visits, 1)),
            in_specs=[pl.BlockSpec((tm, k), lambda *a: (tile_of(*a), 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn), lambda *a: (tile_of(*a), a[0])),
            scratch_shapes=[pltpu.VMEM((2, len(columns), k, tn), rhs.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles_m * tm, width), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=held + (8 << 20)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*prefetch, lhs, rhs)
    return out[:m]


def grouped_matmul(lhs, rhs, group_sizes, *, gated: bool = False):
    """``lhs`` [M, K] rows sorted by group, ``rhs`` [G, K, N] -> [M, N]
    float32 (``gated``: ``silu`` of the first half of the columns times the
    second, [M, N/2] in ``lhs``'s dtype).  Rows past the groups' total are
    not defined: the caller masks them.  The Pallas kernel on a TPU, XLA's
    ``ragged_dot`` elsewhere (:func:`grouped_matmul_impl`)."""
    if grouped_matmul_impl() == "pallas":
        return grouped_matmul_pallas(lhs, rhs, group_sizes, gated=gated)
    return grouped_matmul_xla(lhs, rhs, group_sizes, gated=gated)
