"""Paged latent attention: the decode step of multi-head latent
attention (MLA, the DeepSeek-V3 block) over a paged cache that holds a
position's compressed latent and its shared rotary key, and nothing per
head.

The cache.  A layer keeps one pool ``[num_blocks, block_size,
row_width(latent + rope)]``, read through one block table a sequence
(`serving/kv_cache.py::LatentKVCache`): a position's row is the normed
latent ``c`` (512 wide at the published sizes), then the rotated key
``k_pe`` that every query head shares (64), then zeros to the next
whole 128-lane tile (`row_width`: 640).  576 numbers a layer carry the
position, where per-head keys and values would take ``heads * (192 +
128)``; the chip holds a row in whole tiles whatever its stated width
(two pools of 512 and 64 would take the same 640), and a kernel's copy
can only name whole tiles, so the pool states them.

The absorbed form.  With ``W_UK`` and ``W_UV`` a head's slices of
``kv_b_proj`` (``k_nope = c W_UK``, ``v = c W_UV``), the caller folds
``W_UK`` into its query (``q_lat = q_nope W_UK^T``, latent wide) and
``W_UV`` into what comes back, so a head's scores and output are

    s = scale * ([q_lat ; q_pe ; 0] . [c ; k_pe ; 0])    o_lat = softmax(s) c

over the positions a row holds: the key is the pool's whole row, the
value its first `latent` lanes.  This module computes ``o_lat [S,
heads, latent]``; the caller multiplies it by ``W_UV``.

Two paths, one signature; `can_use_pallas_latent` chooses by what it
can observe (a TPU or interpret mode, no mesh, the widths):

- **`paged_decode_latent`, the Pallas kernel**: one grid step a row.
  The row's blocks are read in place through its table, up to
  ``cdiv(len, block_size)``, a round of blocks an async copy into one
  half of a double buffer while the body works on the other (the round
  after a row's last fetches the next row's first, as
  `paged_decode_grouped` does).  The row's heads lie on the sublanes
  (32 heads: four float32 tiles) and a round's positions on the lanes:
  the scores ``[heads, positions]`` are one matmul against the round's
  keys transposed, and the output ``[heads, latent]`` one more, the
  probabilities against the first `latent` lanes of the same keys, with
  an online softmax in float32.  Float32 operands, one MXU pass
  (Mosaic's default), as the other paged kernels'.
- **the reference** (`_reference_latent`): gather every block of the
  table into a dense copy and attend over it, at the highest matmul
  precision.

Contract (tests/test_latent_attention.py): within 1e-5 relative of the
reference in float32; a row's result depends on its own queries, table
row and length only, bitwise; nothing past a length, NaN included,
reaches the result.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _gating
from .paged_attention import NEG_INF, ROUND_BYTES

# the tables and lengths ride in SMEM (scalar prefetch), 1 MiB on the
# v5e: a described-chip compile takes 48 tables of 4,096 and refuses 48
# of 16,384; half of it is the bound (48 rows of 18,432 positions in
# blocks of 16 are 221 KB)
MAX_TABLE_ENTRIES = 1 << 17

__all__ = ['row_width', 'to_row', 'write_latent', 'latent_attention',
           'can_use_pallas_latent']


def row_width(numbers):
    """A pool row's width: `numbers` rounded up to whole 128-lane tiles."""
    return -(-int(numbers) // 128) * 128


def to_row(x, width):
    """`x [..., n]` zero-padded on its last axis to `width`."""
    pad = width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def write_latent(pool, row, block_tables, slots):
    """Scatter one new position a row into the pool: `row [S, latent +
    rope]` (the latent, then the rotated key; zero-padded to the pool's
    row) at the absolute position `slots [S]` through `block_tables [S,
    width]`.  A row whose table names the trash block writes there
    (`write_kv`'s rule)."""
    bs = pool.shape[1]
    blk = jnp.take_along_axis(block_tables, (slots // bs).astype(
        jnp.int32)[:, None], axis=1)[:, 0]
    off = (slots % bs).astype(jnp.int32)
    return pool.at[blk, off].set(
        to_row(row, pool.shape[-1]).astype(pool.dtype))


def _reference_latent(q, pool, block_tables, lens, latent, scale):
    """Gather and attend: every block a table names, columns [0, lens).
    `q [S, heads, pool row]` is `[q_lat ; q_pe ; 0]`."""
    S, width = block_tables.shape
    bs = pool.shape[1]
    with jax.named_scope('paged.gather_dense'):
        k = pool[block_tables].reshape(S, width * bs, -1)
    live = jnp.arange(width * bs, dtype=lens.dtype)[None] < lens[:, None]
    # what lies past a length may be anything: 0 * NaN is NaN
    k = jnp.where(live[:, :, None], k, 0.0).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum('shd,std->sht', q.astype(jnp.float32), k,
                   precision=hi) * scale
    p = jax.nn.softmax(jnp.where(live[:, None], s, -1e9), axis=-1)
    return jnp.einsum('sht,stc->shc', p, k[..., :latent], precision=hi)


def can_use_pallas_latent(pool, block_tables, num_heads, latent):
    """True iff `latent_attention` takes the Pallas kernel: a TPU (or
    interpret mode) and no mesh, a float32 or bfloat16 pool, a latent of
    whole 128-lane vregs, a block of whole sublane tiles, the heads
    whole float32 sublane tiles, one fetch round inside its VMEM
    budget, the tables inside SMEM."""
    _, bs, _ = pool.shape
    if pool.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return (_gating.pallas_backend_ok()
            and latent % 128 == 0
            and bs % (8 * 4 // jnp.dtype(pool.dtype).itemsize) == 0
            and num_heads % 8 == 0
            and _blocks_a_round(pool, block_tables) >= 1
            and block_tables.size <= MAX_TABLE_ENTRIES)


def _blocks_a_round(pool, block_tables):
    """`ROUND_BYTES` of keys a round, as the other paged kernels' (25
    blocks of a float32 pool of rows of 640)."""
    _, bs, width = pool.shape
    block_bytes = bs * width * jnp.dtype(pool.dtype).itemsize
    return min(ROUND_BYTES // block_bytes, block_tables.shape[1])


def _latent_kernel(tbl_ref, lens_ref, q_ref, k_hbm, o_ref, k_buf, sems,
                   slot_ref, *, width):
    """One grid step = one row (this file's header).  A round whose
    positions all lie inside the row's length, every one but its last
    as a rule, runs with no mask."""
    row = pl.program_id(0)
    rows = pl.num_programs(0)
    chunk, bs, dk = k_buf.shape[1:]
    latent = o_ref.shape[2]
    n_pos = chunk * bs

    def blocks_of(r):
        # never more than the table holds, never none
        return jnp.clip((lens_ref[r] + bs - 1) // bs, 1, width)

    def copies(r, j, slot, start):
        n = blocks_of(r)
        for c in range(chunk):
            i = j * chunk + c

            @pl.when(i < n)
            def _():
                cp = pltpu.make_async_copy(
                    k_hbm.at[tbl_ref[r * width + i]], k_buf.at[slot, c],
                    sems.at[slot])
                cp.start() if start else cp.wait()

    @pl.when(row == 0)
    def _():
        slot_ref[0] = 0
        copies(0, 0, 0, start=True)

    slot0 = slot_ref[0]
    n_rounds = (blocks_of(row) + chunk - 1) // chunk
    length = jnp.minimum(lens_ref[row], width * bs)

    def attend(carry, slot, start, masked):
        m, l, acc = carry
        k = k_buf[slot].reshape(n_pos, dk).astype(jnp.float32)
        if masked:
            lanes = jax.lax.broadcasted_iota(jnp.int32, (1, n_pos), 1)
            subl = jax.lax.broadcasted_iota(jnp.int32, (n_pos, 1), 0)
            live = lanes + start < length
            # what the buffer holds past the length was never fetched:
            # it may be anything, and 0 * NaN is NaN
            k = jnp.where(subl + start < length, k, 0.0)
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(live, s, NEG_INF)                # [heads, n_pos]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(live, p, 0.0)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p, k[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [heads, latent]
        return m_new, l, acc

    def body(j, carry):
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < n_rounds)
        def _():
            copies(row, j + 1, 1 - slot, start=True)

        @pl.when((j + 1 == n_rounds) & (row + 1 < rows))
        def _():
            copies(row + 1, 0, 1 - slot, start=True)

        copies(row, j, slot, start=False)
        start = j * n_pos
        return jax.lax.cond(
            start + n_pos <= length,
            functools.partial(attend, masked=False),
            functools.partial(attend, masked=True), carry, slot, start)

    heads = q_ref.shape[1]
    m, l, acc = jax.lax.fori_loop(
        0, n_rounds, body,
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, latent), jnp.float32)))
    slot_ref[0] = (slot0 + n_rounds) % 2
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('latent', 'scale', 'chunk',
                                             'interpret'))
def _paged_decode_latent(q, pool, block_tables, lens, *, latent, scale,
                         chunk, interpret=False):
    S, heads, dk = q.shape
    _, bs, _ = pool.shape
    width = block_tables.shape[1]
    kernel = functools.partial(_latent_kernel, width=width)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, heads, dk), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, latent),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, bs, dk), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, heads, latent), jnp.float32),
        # rows run in order: the double buffer's state crosses them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
        name='paged_decode_latent',
    )(block_tables.reshape(-1).astype(jnp.int32), lens.astype(jnp.int32),
      q.astype(jnp.float32) * scale, pool)


def latent_attention(q_lat, q_pe, pool, block_tables, lens, scale):
    """One decode step of absorbed latent attention over the paged pool
    (this file's header).

    q_lat        : [S, heads, latent]  q_nope with W_UK folded in
    q_pe         : [S, heads, rope]    the rotated rotary queries
    pool         : [num_blocks, block_size, row_width(latent + rope)]
    block_tables : [S, width] int, every entry a block of the pool
    lens         : [S] int (>= 1), the positions a row holds, the one
                   just written among them
    scale        : the softmax's scale, static

    -> o_lat [S, heads, latent] float32."""
    latent = q_lat.shape[-1]
    q = to_row(jnp.concatenate([q_lat.astype(jnp.float32),
                                q_pe.astype(jnp.float32)], -1),
               pool.shape[-1])
    if can_use_pallas_latent(pool, block_tables, q.shape[1], latent):
        return _paged_decode_latent(
            q, pool, block_tables, lens, latent=latent, scale=float(scale),
            chunk=_blocks_a_round(pool, block_tables),
            interpret=_gating.INTERPRET)
    return _reference_latent(q, pool, block_tables, lens, latent, scale)
