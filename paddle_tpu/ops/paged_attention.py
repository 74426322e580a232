"""Ragged paged attention — the serving-decode kernel (RPA-style).

Reference: "Ragged Paged Attention: A High-Performance and Flexible
LLM Inference Kernel for TPU" (PAPERS.md, arxiv 2604.15464).  The
serving KV cache lives in FIXED-SIZE blocks inside one preallocated
pool (``serving/kv_cache.py``); each sequence owns a *block table* —
a row of pool indices — and a ragged length.  One decode step then
attends a whole batch of wildly different-length sequences at once:
gather each sequence's blocks through its table, mask columns past
its length, softmax, weight.

Numerics contract (pinned by test): **bit-exact vs the dense cached
attention** in ``models/gpt.py`` on the same keys/values.  Masked
columns score ``-1e9`` exactly as the dense path does, so after the
softmax's max-subtraction they underflow to exact ``0.0`` and the
extra (block-padded) lanes contribute exact zeros to every reduction
— the same argument that made PR 7's pow2 prompt bucketing bit-exact.
Valid columns occupy the same leading positions in the same order as
the dense buffer, so reduction trees agree on the real lanes.

This file is the portable jnp reference implementation (gathers
materialize [S, max_blocks*block_size] keys per layer).  On real TPU
the gather stays in HBM-friendly shape; a Pallas RPA kernel that
streams blocks without materializing the gather is the planned drop-in
(see ops/flash_attention.py for the kernel-vs-reference layering this
module will follow).
"""
import math

__all__ = ['write_kv', 'paged_attention', 'gather_dense', 'POOL_SPEC']

# sharding of one layer's pool [num_blocks, num_heads, block_size,
# head_dim]: heads ride the tp axis (same Megatron head split as the
# attention weights), blocks/positions replicated
POOL_SPEC = (None, 'tp', None, None)


def write_kv(k_pool, v_pool, k_new, v_new, block_tables, slots):
    """Scatter one new token's k/v per sequence into the paged pool.

    k_pool/v_pool : [num_blocks, num_heads, block_size, head_dim]
    k_new/v_new   : [S, num_heads, head_dim] — this step's k/v rows
    block_tables  : [S, max_blocks] int — pool indices per sequence
    slots         : [S] int — the ABSOLUTE position being written
                    (= the sequence's context length before this token)

    Returns the updated (k_pool, v_pool).  Rows whose table entry is
    the reserved trash block (0) land there harmlessly — that is how
    inactive batch slots stay in the compiled step without corrupting
    live sequences.
    """
    import jax
    import jax.numpy as jnp
    with jax.named_scope('paged.write_kv'):
        bs = k_pool.shape[2]
        idx = (slots // bs).astype(jnp.int32)
        bids = jnp.take_along_axis(
            block_tables, idx[:, None], axis=1)[:, 0]
        offs = (slots % bs).astype(jnp.int32)
        k_pool = k_pool.at[bids, :, offs].set(
            k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[bids, :, offs].set(
            v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def gather_dense(pool, block_table):
    """One sequence-major dense view of the pooled cache:
    [num_blocks, nh, bs, hd] gathered through [S, max_blocks] tables
    -> [S, nh, max_blocks*bs, hd] (position-contiguous per sequence).
    """
    import jax
    import jax.numpy as jnp
    S, mb = block_table.shape
    _, nh, bs, hd = pool.shape
    with jax.named_scope('paged.gather_dense'):
        g = pool[block_table]                  # [S, mb, nh, bs, hd]
        g = jnp.transpose(g, (0, 2, 1, 3, 4))  # [S, nh, mb, bs, hd]
        return g.reshape(S, nh, mb * bs, hd)


def paged_attention(q, k_pool, v_pool, block_tables, lens):
    """One ragged decode step of attention over the paged cache.

    q            : [S, num_heads, head_dim] — ONE query token per
                   sequence (the continuous-batching decode shape)
    k_pool/v_pool: [num_blocks, num_heads, block_size, head_dim]
    block_tables : [S, max_blocks] int
    lens         : [S] int — valid context length per sequence,
                   INCLUDING the token just written via ``write_kv``

    -> [S, num_heads, head_dim].

    Mirrors the dense cached path in models/gpt.py operation for
    operation (same 1/sqrt(hd) scale, same -1e9 mask fill, same
    softmax) so the two are bit-exact on shared prefixes.
    """
    import jax
    import jax.numpy as jnp
    hd = q.shape[-1]
    with jax.named_scope('paged.attention'):
        k = gather_dense(k_pool, block_tables)  # [S, nh, mb*bs, hd]
        v = gather_dense(v_pool, block_tables)
        scores = jnp.einsum('shd,shkd->shk', q, k) \
            * (1.0 / math.sqrt(hd))
        cols = jnp.arange(k.shape[2], dtype=lens.dtype)
        mask = cols[None, :] < lens[:, None]    # ragged, per sequence
        scores = jnp.where(mask[:, None, :], scores, -1e9)
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum('shk,shkd->shd', att, v)
