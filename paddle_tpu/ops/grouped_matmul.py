"""Grouped matmul: rows sorted by group, each group's rows against its
own matrix (a routed layer's prefill: groups are experts).

    out[r] = rows[r] @ w[g]      for offsets[g] <= r < offsets[g + 1]
    out[r] = 0                   for r >= offsets[E]  (rows no group holds)

`rows [M, K]` and `w [E, K, N]` in bfloat16, `sizes [E]` int32 with
`sizes.sum() <= M`, float32 accumulation.  Two entry points, one kernel
body: `grouped_matmul` returns `[M, N]` float32; `grouped_gate_up` reads
a row tile once against TWO matrices and returns `act(rows @ wg[g]) *
(rows @ wu[g])` rounded once to `dtype`, so the two float32 products
never reach HBM; `act` is the caller's (`ACTIVATIONS`: a ReGLU or a
SwiGLU expert), a static argument of the one kernel.

**Visits.**  The grid is one list of visits, a (group, row tile) pair
each, in the rows' order: a group of `n` rows starting at `s` visits the
tiles `s // tm .. (s + n - 1) // tm`, so a tile two groups share is
visited once by each and a group's rows cost `ceil` of their own span,
never a fixed 512.  The list is computed on the device from `sizes`
(`group_metadata`) and handed to the kernel by scalar prefetch; it is at
most `M // tm + E - 1` long, the grid's static length.  A visit stores
the rows of its own group and leaves the tile's others as they are (the
first visit of a tile zeroes them).  Behind the last group's visits come
the tiles no group reaches, once each: nothing is read or multiplied for
them, the tile is stored as zeros.  What is left of the grid does
nothing: its block indices repeat the last visit's, so nothing moves.

**Bytes.**  A group's whole `[K, N]` matrix is one block whose index
stays over the group's consecutive visits: every matrix is read once a
call.  `N` is not tiled (the block has to fit VMEM beside the row and
output tiles: the gate refuses what does not).

**The tile height** is 128 rows (`TILE_ROWS`).  On the v5e at the
routed decoder's widths (64 groups, 2560 x 768 and back) tiles of 256
read within 2% of it at every prompt bucket from 1,024 to 12,288 and
behind it at most (PERF.md, PR 34): what a taller tile saves in grid
steps it loses to the tiles two groups share.  `_grouped` takes the
height as an argument; the tests run both.

`can_use_pallas` is the gate (a TPU or interpret mode, no mesh, the
shapes above); the caller keeps `jax.lax.ragged_dot` for what it
refuses.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _gating

__all__ = ['can_use_pallas', 'TILE_ROWS', 'ACTIVATIONS', 'group_metadata',
           'grouped_matmul', 'grouped_gate_up']

F32 = jnp.float32
# what one call's blocks may hold of VMEM (the v5e has 128 MiB; the
# compiler's default scope is 16): the gate refuses a larger matrix
VMEM_BUDGET = 48 << 20
TILE_ROWS = 128
# what a gated pair's epilogue may apply to the gate's product, as
# the kernel's body writes it
ACTIVATIONS = {'relu': lambda x: jnp.maximum(x, 0.0),
               'silu': lambda x: x * jax.nn.sigmoid(x)}


def _vmem_bytes(tm, k, n, weights, out_bytes):
    """Double-buffered blocks of one call and the body's float32
    accumulators."""
    return (2 * tm * k * 2 + 2 * weights * k * n * 2
            + 2 * tm * n * out_bytes + (weights + 1) * tm * n * 4)


def can_use_pallas(m, w, matrices=1):
    """True iff the grouped product of `m` rows (in `w`'s dtype) against
    `matrices` stacks shaped like `w [E, K, N]` takes the Pallas
    kernel: a TPU (or interpret mode) and no mesh, bfloat16, widths
    that are whole 128-lane vregs, whole tiles of rows, blocks that fit
    VMEM."""
    if not _gating.pallas_backend_ok() or w.ndim != 3 \
            or w.dtype != jnp.bfloat16:
        return False
    _, k, n = w.shape
    return (m % TILE_ROWS == 0 and k % 128 == 0 and n % 128 == 0
            and _vmem_bytes(TILE_ROWS, k, n, matrices, 4) <= VMEM_BUDGET)


def group_metadata(sizes, m, tm):
    """The visits of `m` rows in tiles of `tm` for groups of `sizes`
    rows: `(offsets [E+1], group [V], tile [V], src [V], real [1])`
    with `V = m // tm + E - 1`.  Step `i < real` multiplies row tile
    `tile[i]` by group `group[i]`'s matrix; the steps behind them name
    the tiles no group reaches, once each and in order, then repeat the
    last tile; `src` is the row tile a step reads (behind the real
    visits the last one read, so nothing is fetched)."""
    E = sizes.shape[0]
    tiles = m // tm
    steps = tiles + E - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first_tile = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    first_visit = jnp.cumsum(spans) - spans
    real = spans.sum()
    i = jnp.arange(steps, dtype=jnp.int32)
    group = jnp.repeat(jnp.arange(E, dtype=jnp.int32), spans,
                       total_repeat_length=steps)
    tile = first_tile[group] + i - first_visit[group]
    visiting = i < real
    last = jnp.maximum(real - 1, 0)
    reached = (ends[-1] + tm - 1) // tm
    behind = jnp.minimum(reached + i - real, tiles - 1)
    group = jnp.where(visiting, group, group[last])
    src = jnp.where(visiting, tile, tile[last])
    return (offsets, group, jnp.where(visiting, tile, behind), src,
            real[None])


def _kernel(offsets, group, tile, src, real, x_ref, *refs, tm, activation):
    """One grid step: a visit (its group's rows of the tile stored),
    a tile no group reaches (zeros), or nothing."""
    del src                                  # the index maps read it
    *w_refs, o_ref = refs
    i = pl.program_id(0)
    t = tile[i]
    first = jnp.logical_or(i == 0, t != tile[jnp.maximum(i - 1, 0)])
    visiting = i < real[0]

    @pl.when(visiting)
    def _():
        x = x_ref[...]
        # a bfloat16 product is exact in float32: one pass, whatever
        # the caller's jax_default_matmul_precision asks of others
        acc = [jnp.dot(x, w[...], precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=F32) for w in w_refs]
        y = ACTIVATIONS[activation](acc[0]) * acc[1] if activation \
            else acc[0]
        g = group[i]
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = jnp.logical_and(row >= offsets[g], row < offsets[g + 1])
        # a tile's first visit finds whatever the buffer held
        kept = jnp.where(first, jnp.zeros(o_ref.shape, o_ref.dtype),
                         o_ref[...])
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), kept)

    @pl.when(jnp.logical_and(jnp.logical_not(visiting), first))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('tm', 'dtype', 'activation',
                                             'interpret'))
def _grouped(rows, ws, sizes, *, tm, dtype, activation='relu',
             interpret=False):
    """One matrix a group: the product.  Two: `activation` of the
    first's product times the second's."""
    m, k = rows.shape
    E, _, n = ws[0].shape
    meta = group_metadata(sizes, m, tm)
    out_bytes = jnp.dtype(dtype).itemsize

    def row_tile(i, offsets, group, tile, src, real):
        return (src[i], 0)

    def matrix(i, offsets, group, tile, src, real):
        return (group[i], 0, 0)

    def out_tile(i, offsets, group, tile, src, real):
        return (tile[i], 0)

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm,
                          activation=activation if len(ws) == 2 else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(m // tm + E - 1,),
            in_specs=[pl.BlockSpec((tm, k), row_tile)]
            + [pl.BlockSpec((None, k, n), matrix) for _ in ws],
            out_specs=pl.BlockSpec((tm, n), out_tile)),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        compiler_params=pltpu.CompilerParams(
            # a tile's visits follow one another and share its buffer
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_vmem_bytes(tm, k, n, len(ws), out_bytes)
            + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(ws),
            transcendentals=m * n if len(ws) == 2
            and activation == 'silu' else 0,
            bytes_accessed=m * k * 2 + len(ws) * E * k * n * 2
            + m * n * out_bytes),
        interpret=interpret,
        name='grouped_gate_up' if len(ws) == 2 else 'grouped_matmul',
    )(*meta, rows, *ws)


def grouped_matmul(rows, w, sizes):
    """`rows [M, K] @ w[g] -> [M, N]` float32 by group (this file's
    header); the caller has asked `can_use_pallas`."""
    return _grouped(rows, (w,), sizes, tm=TILE_ROWS, dtype=F32,
                    interpret=_gating.INTERPRET)


def grouped_gate_up(rows, wg, wu, sizes, dtype, activation='relu'):
    """`act(rows @ wg[g]) * (rows @ wu[g]) -> [M, N]` in `dtype`, by
    group, the two float32 products multiplied and rounded once;
    `activation` names `act` (`ACTIVATIONS`)."""
    return _grouped(rows, (wg, wu), sizes, tm=TILE_ROWS,
                    dtype=jnp.dtype(dtype), activation=activation,
                    interpret=_gating.INTERPRET)
