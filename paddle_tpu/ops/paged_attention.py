"""Ragged paged attention — the serving-decode kernel (RPA-style).

Reference: "Ragged Paged Attention: A High-Performance and Flexible
LLM Inference Kernel for TPU" (PAPERS.md, arxiv 2604.15464).  The
serving KV cache lives in FIXED-SIZE blocks inside one preallocated
pool (``serving/kv_cache.py``); each sequence owns a *block table* —
a row of pool indices — and a ragged length.  One decode step then
attends a whole batch of wildly different-length sequences at once:
read each sequence's blocks through its table, mask columns past
its length, softmax, weight.

The pool's order.  One layer's pool is stored, stated and passed as
``[num_blocks, block_size, num_heads, head_dim]``: positions outside
heads.  That is the order `write_kv`'s scatter writes (one
``[num_heads, head_dim]`` row at a (block, position)), the order a
decode module's ``lax.scan`` carries and the order the kernel's async
copies read a block in, so a module's arguments, its carry and its
results have one layout and XLA has nothing to copy round the scan.
(Stated with heads outside positions, as until PR 28, XLA kept the
carry in this order all the same and copied every pool in front of
the scan and back behind it: a quarter to a third of an intervention,
PERF.md section 6, PR 28.  PR 26's finding — a kernel has to read its
operand in the layout the scan carries — now holds by construction.)

Two paths, one signature; `can_use_pallas` chooses by what it can
observe (a TPU or interpret mode, no mesh, the shapes below):

- **`paged_decode`, the Pallas kernel** (the chip's path): each
  sequence's K and V blocks are read *in place* from the pool in HBM
  through its block-table row, a few blocks an async copy round into a
  double buffer, only up to ``cdiv(len, block_size)`` blocks — nothing
  past a sequence's length is fetched, an inactive slot costs one
  block — with an online softmax in float32 on the VPU.  No copy of
  the cache is made.  Contract (tests/test_paged_attention_kernel.py):
  within 1e-5 relative of the reference in float32; a row's result
  depends on that row's q, table row and length only, bitwise, never
  on the other rows or the batch bucket; what lies past a length,
  NaN included, cannot reach the result.
- **the reference** (`gather_dense` and dense attention over its
  copy: the CPU, a mesh, shapes the gate refuses, and the oracle of
  the tests).  Contract (pinned by test): **bit-exact vs the dense
  cached attention** in ``models/gpt.py`` on the same keys/values.
  Masked columns score ``-1e9`` exactly as the dense path does, so
  after the softmax's max-subtraction they underflow to exact ``0.0``
  and the extra (block-padded) lanes contribute exact zeros to every
  reduction — the same argument that made PR 7's pow2 prompt bucketing
  bit-exact.  Valid columns occupy the same leading positions in the
  same order as the dense buffer, so reduction trees agree on the real
  lanes.  It materializes [S, max_blocks*block_size] keys and values
  per layer at the table's full width whatever a sequence's length.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _gating

__all__ = ['write_kv', 'paged_attention', 'gather_dense',
           'can_use_pallas', 'POOL_SPEC']

# sharding of one layer's pool [num_blocks, block_size, num_heads,
# head_dim]: heads ride the tp axis (same Megatron head split as the
# attention weights), blocks/positions replicated
POOL_SPEC = (None, None, 'tp', None)


def write_kv(k_pool, v_pool, k_new, v_new, block_tables, slots):
    """Scatter one new token's k/v per sequence into the paged pool.

    k_pool/v_pool : [num_blocks, block_size, num_heads, head_dim]
    k_new/v_new   : [S, num_heads, head_dim] — this step's k/v rows
    block_tables  : [S, max_blocks] int — pool indices per sequence
    slots         : [S] int — the ABSOLUTE position being written
                    (= the sequence's context length before this token)

    Returns the updated (k_pool, v_pool).  Rows whose table entry is
    the reserved trash block (0) land there harmlessly — that is how
    inactive batch slots stay in the compiled step without corrupting
    live sequences.
    """
    with jax.named_scope('paged.write_kv'):
        bs = k_pool.shape[1]
        idx = (slots // bs).astype(jnp.int32)
        bids = jnp.take_along_axis(
            block_tables, idx[:, None], axis=1)[:, 0]
        offs = (slots % bs).astype(jnp.int32)
        k_pool = k_pool.at[bids, offs].set(k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[bids, offs].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def gather_dense(pool, block_table):
    """One sequence-major dense view of the pooled cache:
    [num_blocks, bs, nh, hd] gathered through [S, max_blocks] tables
    -> [S, nh, max_blocks*bs, hd] (position-contiguous per sequence).
    """
    S, mb = block_table.shape
    _, bs, nh, hd = pool.shape
    with jax.named_scope('paged.gather_dense'):
        g = pool[block_table]                  # [S, mb, bs, nh, hd]
        g = jnp.transpose(g, (0, 3, 1, 2, 4))  # [S, nh, mb, bs, hd]
        return g.reshape(S, nh, mb * bs, hd)


def _reference(q, k_pool, v_pool, block_tables, lens):
    """The portable path: gather each table row's blocks into a dense
    copy and attend over it.  Mirrors the dense cached path in
    models/gpt.py operation for operation (same 1/sqrt(hd) scale, same
    -1e9 mask fill, same softmax) so the two are bit-exact on shared
    prefixes."""
    hd = q.shape[-1]
    k = gather_dense(k_pool, block_tables)      # [S, nh, mb*bs, hd]
    v = gather_dense(v_pool, block_tables)
    scores = jnp.einsum('shd,shkd->shk', q, k) \
        * (1.0 / math.sqrt(hd))
    cols = jnp.arange(k.shape[2], dtype=lens.dtype)
    mask = cols[None, :] < lens[:, None]        # ragged, per sequence
    scores = jnp.where(mask[:, None, :], scores, -1e9)
    att = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('shk,shkd->shd', att, v)


# -- the Pallas decode kernel ------------------------------------------------
# Bytes of K, and again of V, that one async copy round fetches: 8
# blocks of a float32 pool at 16 positions x 16 heads x 128.  Twice
# for the double buffer: 4 MB of the 16 MB scoped VMEM default, the
# rest is the body's own temporaries.
ROUND_BYTES = 1 << 20
# tables and lens ride in SMEM (scalar prefetch)
_MAX_TABLE_ENTRIES = 1 << 15
NEG_INF = -1e30


def can_use_pallas(k_pool, block_tables):
    """True iff `paged_attention` takes the Pallas kernel for these
    operands: a TPU (or interpret mode) and no mesh, and the shapes the
    kernel was written for — head_dim a whole number of 128-lane
    vregs, the heads a whole number of sublane tiles of the pool's
    dtype, one fetch round inside its VMEM budget, the tables inside
    SMEM.  Everything else takes the reference path."""
    _, bs, nh, hd = k_pool.shape
    if k_pool.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return (_gating.pallas_backend_ok()
            and hd % 128 == 0
            and bs % 8 == 0
            and nh % (8 * 4 // jnp.dtype(k_pool.dtype).itemsize) == 0
            and _blocks_a_round(k_pool, block_tables) >= 1
            and block_tables.size <= _MAX_TABLE_ENTRIES)


def _blocks_a_round(k_pool, block_tables):
    _, bs, nh, hd = k_pool.shape
    block_bytes = nh * bs * hd * jnp.dtype(k_pool.dtype).itemsize
    return min(ROUND_BYTES // block_bytes, block_tables.shape[1])


def _decode_kernel(tbl_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, slot_ref, *, width, scale):
    """One grid step = one sequence.  Its K and V blocks come straight
    from the pool in HBM through its block-table row, `chunk` blocks a
    round into one half of a double buffer while the body works on the
    other half; the round after a row's last fetches the next row's
    first, so the copies never drain between rows.

    Each of a block's `bs` positions keeps its own online-softmax
    state (m, l, acc) across the row's blocks, so the loop is
    elementwise over whole blocks; the bs states of a head merge once
    at the row's end.  All float32 on the VPU.
    """
    row = pl.program_id(0)
    rows = pl.num_programs(0)
    chunk, bs, nh, hd = k_buf.shape[1:]

    def blocks_of(r):
        # never more than the table holds, never none: the first
        # block is always fetched (an inactive slot costs one block)
        return jnp.clip((lens_ref[r] + bs - 1) // bs, 1, width)

    def copies(r, j, slot, start):
        """Start (or wait for) round j of row r into `slot`: only
        blocks inside the row's length are ever touched."""
        n = blocks_of(r)
        for c in range(chunk):
            i = j * chunk + c

            @pl.when(i < n)
            def _():
                bid = tbl_ref[r * width + i]
                for kv, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(
                        hbm.at[bid], buf.at[slot, c], sems.at[kv, slot])
                    cp.start() if start else cp.wait()

    @pl.when(row == 0)
    def _():
        slot_ref[0] = 0
        copies(0, 0, 0, start=True)

    slot0 = slot_ref[0]
    n_blocks = blocks_of(row)
    n_rounds = (n_blocks + chunk - 1) // chunk
    length = jnp.minimum(lens_ref[row], width * bs)
    q = q_ref[0].astype(jnp.float32) * scale          # [nh, hd]
    # position of (c, t) inside a round
    offs = jax.lax.broadcasted_iota(jnp.int32, (chunk, bs, 1, 1), 0) \
        * bs + jax.lax.broadcasted_iota(jnp.int32, (chunk, bs, 1, 1), 1)

    def body(j, carry):
        m, l, acc = carry
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < n_rounds)
        def _():
            copies(row, j + 1, 1 - slot, start=True)

        @pl.when((j + 1 == n_rounds) & (row + 1 < rows))
        def _():
            copies(row + 1, 0, 1 - slot, start=True)

        copies(row, j, slot, start=False)
        live = offs + j * (chunk * bs) < length       # [chunk,bs,1,1]
        k = k_buf[slot].astype(jnp.float32)           # [chunk,bs,nh,hd]
        s = jnp.sum(k * q, axis=-1, keepdims=True)    # [chunk,bs,nh,1]
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0))    # [bs, nh, 1]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[None])
        # what the buffer holds past the length was never fetched: it
        # may be anything, and 0 * NaN is NaN
        v = jnp.where(live, v_buf[slot].astype(jnp.float32), 0.0)
        l = alpha * l + jnp.sum(p, axis=0)
        acc = alpha * acc + jnp.sum(p * v, axis=0)    # [bs, nh, hd]
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_rounds, body,
        (jnp.full((bs, nh, 1), NEG_INF, jnp.float32),
         jnp.zeros((bs, nh, 1), jnp.float32),
         jnp.zeros((bs, nh, hd), jnp.float32)))
    slot_ref[0] = (slot0 + n_rounds) % 2
    # merge the bs per-position states of each head; a position that
    # never saw a live column has m = NEG_INF and weighs exp(-inf) = 0
    w = jnp.exp(m - jnp.max(m, axis=0, keepdims=True))
    l = jnp.sum(l * w, axis=0)                        # [nh, 1]
    o = jnp.sum(acc * w, axis=0)                      # [nh, hd]
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# A jit of its own: a decode module calls this once a layer with the
# same shapes, and jax then traces the kernel and lowers it to Mosaic
# once a module, not once a layer (24.1 s against 3.5 s of tracing a
# decode module on the chip's host, PERF.md section 6, PR 26).  What
# the trace reads besides its operands is therefore static.
@functools.partial(jax.jit, static_argnames=('chunk', 'interpret'))
def _paged_decode(q, k_pool, v_pool, block_tables, lens, *, chunk,
                  interpret=False):
    S, nh, hd = q.shape
    _, bs, _, _ = k_pool.shape
    width = block_tables.shape[1]
    out_dtype = jnp.result_type(q.dtype, k_pool.dtype)
    kernel = functools.partial(_decode_kernel, width=width,
                               scale=1.0 / math.sqrt(hd))
    row_spec = pl.BlockSpec((1, nh, hd), lambda s, *_: (s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[row_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((2, chunk, bs, nh, hd), k_pool.dtype),
                pltpu.VMEM((2, chunk, bs, nh, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), out_dtype),
        # rows run in order: the double buffer's state crosses them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
        name='paged_decode',
    )(block_tables.reshape(-1).astype(jnp.int32),
      lens.astype(jnp.int32), q, k_pool, v_pool)


def paged_attention(q, k_pool, v_pool, block_tables, lens):
    """One ragged decode step of attention over the paged cache.

    q            : [S, num_heads, head_dim] — ONE query token per
                   sequence (the continuous-batching decode shape)
    k_pool/v_pool: [num_blocks, block_size, num_heads, head_dim]
    block_tables : [S, max_blocks] int — every entry a block of the
                   pool: the kernel copies by them unchecked, where a
                   gather would clamp
    lens         : [S] int (>= 1) — valid context length per sequence,
                   INCLUDING the token just written via ``write_kv``

    -> [S, num_heads, head_dim].

    A row's result depends on that row's q, table row and length only.
    """
    with jax.named_scope('paged.attention'):
        if can_use_pallas(k_pool, block_tables):
            return _paged_decode(
                q, k_pool, v_pool, block_tables, lens,
                chunk=_blocks_a_round(k_pool, block_tables),
                interpret=_gating.INTERPRET)
        return _reference(q, k_pool, v_pool, block_tables, lens)
