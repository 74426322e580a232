"""Ring attention — causal attention over sequence-sharded K/V.

Reference analogue: none in-tree (the reference caps sequence length per
GPU); the brief requires long-sequence support.  Design follows the
ring-attention recipe (Liu et al.; see PAPERS.md): each `sp` shard holds
a T/sp slice of Q/K/V, K/V blocks rotate around the ring via
`lax.ppermute` (XLA schedules the transfers over ICI so step i+1's K/V
moves while step i computes), and a streaming online-softmax merges the
per-block partials — the full [T, T] score matrix never exists and each
chip's attention memory is O((T/sp)^2).

The step body is wrapped in jax.checkpoint so the backward pass
recomputes per-block scores instead of storing every rotated K/V.
"""
import functools
import math

import jax
from jax import shard_map
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ['ring_attention', 'ring_attention_spmd', 'stripe_tokens',
           'unstripe_tokens', 'ring_attention_striped']

NEG_INF = -1e30


def _flash_gate_and_blocks(t_local, d, causal):
    """(ok, bq, bk): may the per-block engine take the Pallas kernel?
    Gates on pallas_tpu_ok, NOT pallas_backend_ok: the ring always runs
    inside a shard_map on an sp-mesh, where the kernel only ever sees
    its local shard (same r3 finding that created
    can_use_pallas_spmd — an installed mesh must not veto)."""
    from ._gating import pallas_tpu_ok
    from .flash_attention import _tuned_blocks, shapes_tile
    bq, bk = _tuned_blocks(t_local, t_local, d, causal)
    bq, bk = min(bq, t_local), min(bk, t_local)
    ok = pallas_tpu_ok() and shapes_tile(t_local, t_local, d, bq, bk)
    return ok, bq, bk


def _merge_lse(acc, part):
    """Streaming merge of (out, lse) partials; the accumulator's lse is
    finite after the home block, so a skipped partial's -inf is safe."""
    o_a, l_a = acc
    o_b, l_b = part
    l_n = jnp.logaddexp(l_a, l_b)
    return (o_a * jnp.exp(l_a - l_n)[..., None]
            + o_b * jnp.exp(l_b - l_n)[..., None], l_n)


def _block_attend(q, k, v, q_chunk, k_chunk, t_local, causal, scale):
    """Partial scores of local q against one rotated K/V block.

    q_chunk/k_chunk are ring positions of the chunks (traced scalars).
    Returns (m, l, o_unnormalized) for online-softmax merging.

    q/k stay in their storage dtype with an f32 MXU accumulator
    (preferred_element_type) and the scale lands on the f32 scores —
    exactly the flash kernel's ordering.  The old operand upcast
    (q.astype(f32) @ k.astype(f32)) forced the ~8x-slower f32 MXU
    path and doubled the rotated blocks' read bytes (tpu-lint
    amp-promotion)."""
    s = jnp.einsum('bqd,bkd->bqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(
            jnp.int32, s.shape[-2:], 0) + q_chunk * t_local
        cols = jax.lax.broadcasted_iota(
            jnp.int32, s.shape[-2:], 1) + k_chunk * t_local
        s = jnp.where(rows[None] >= cols[None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows: exp(NEG_INF - NEG_INF) would be 1 — clamp m
    m = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # p is genuinely f32 (softmax weights): the mixed-precision dot
    # accumulates in f32 without re-reading v as f32 from HBM
    o = jnp.einsum('bqk,bkd->bqd', p, v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def ring_attention(q, k, v, axis_name, causal=True, scale=None,
                   use_flash=None):
    """Attention inside shard_map: q/k/v are the LOCAL [B*H, T/sp, D]
    shards; K/V rotate around `axis_name`.  Returns local output shard.

    Two per-block engines:
    - einsum (default off-TPU): O((T/sp)^2) scores per block, masked.
    - flash (`use_flash`, auto on TPU when the local shapes tile): each
      visible block runs the Pallas kernel via flash_attention_lse and
      partials merge in (out, lse) space — per-block memory drops to
      O(block) and the kernel skips masked tiles, so the diagonal block
      costs half.  Fully-masked future blocks skip compute entirely in
      BOTH engines (lax.cond/switch on the rotated chunk index).
    """
    sp = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    gate_ok, fbq, fbk = _flash_gate_and_blocks(t_local, q.shape[-1],
                                               causal)
    if use_flash is None:
        use_flash = gate_ok
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal, scale, sp, rank,
                           t_local, fbq, fbk)

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def merge(acc, part):
        m_acc, l_acc, o_acc = acc
        m, l, o = part
        m_new = jnp.maximum(m_acc, m)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m - m_new)
        return (m_new, l_acc * alpha + l * beta,
                o_acc * alpha + o * beta)

    def skipped(kb, vb):
        # identity partial under merge (m=NEG_INF => beta==0)
        shp = (q.shape[0], t_local, 1)
        return (jnp.full(shp, NEG_INF, jnp.float32),
                jnp.zeros(shp, jnp.float32),
                jnp.zeros(q.shape, jnp.float32))

    @jax.checkpoint
    def step(carry, i):
        m_acc, l_acc, o_acc, kb, vb = carry
        # rotate first (step i holds a block i hops from home); the last
        # block is consumed without a trailing, wasted ppermute
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        k_chunk = (rank - i) % sp
        if causal:
            # future chunks are fully masked — skip their FLOPs
            part = jax.lax.cond(
                k_chunk > rank, skipped,
                lambda kb, vb: _block_attend(q, kb, vb, rank, k_chunk,
                                             t_local, causal, scale),
                kb, vb)
        else:
            part = _block_attend(q, kb, vb, rank, k_chunk, t_local,
                                 causal, scale)
        m_acc, l_acc, o_acc = merge((m_acc, l_acc, o_acc), part)
        return (m_acc, l_acc, o_acc, kb, vb), None

    # step 0: the home block, no rotation needed
    acc = _block_attend(q, k, v, rank, rank, t_local, causal, scale)
    (m_acc, l_acc, o_acc, _, _), _ = jax.lax.scan(
        step, acc + (k, v), jnp.arange(1, sp))
    out = o_acc / jnp.maximum(l_acc, 1e-30)
    return out.astype(q.dtype)


def _ring_flash(q, k, v, axis_name, causal, scale, sp, rank, t_local,
                bq, bk):
    """Flash-blocked ring: every visible block is one Pallas kernel
    call; partials merge in (out, lse) space.  The lse gradient is
    exact through flash_attention_lse's custom vjp."""
    from .flash_attention import flash_attention_lse
    f32 = jnp.float32

    def full_blk(kb, vb):
        o, l = flash_attention_lse(q, kb, vb, False, scale, bq, bk)
        return o.astype(f32), l

    def diag_blk(kb, vb):
        o, l = flash_attention_lse(q, kb, vb, True, scale, bq, bk)
        return o.astype(f32), l

    def skip_blk(kb, vb):
        return (jnp.zeros(q.shape, f32),
                jnp.full(q.shape[:2], -jnp.inf, f32))

    merge = _merge_lse
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    @jax.checkpoint
    def step(carry, i):
        o_acc, l_acc, kb, vb = carry
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        k_chunk = (rank - i) % sp
        if causal:
            part = jax.lax.cond(k_chunk > rank, skip_blk, full_blk,
                                kb, vb)
        else:
            part = full_blk(kb, vb)
        o_acc, l_acc = merge((o_acc, l_acc), part)
        return (o_acc, l_acc, kb, vb), None

    o0, l0 = diag_blk(k, v) if causal else full_blk(k, v)
    (o_acc, l_acc, _, _), _ = jax.lax.scan(
        step, (o0, l0, k, v), jnp.arange(1, sp))
    return o_acc.astype(q.dtype)


def stripe_tokens(x, sp, axis=1):
    """Natural -> striped token order: token t = i*sp + s moves to
    position s*(T/sp) + i, so a contiguous shard s over `axis` holds
    the STRIDED tokens {s, s+sp, s+2sp, ...}.  Apply once at the model
    boundary (ids in, logits/labels out) — attention is the only
    position-coupled op, so the hidden states can live striped."""
    T = x.shape[axis]
    t_local = T // sp
    shape = list(x.shape)
    x = jnp.moveaxis(x, axis, 0)
    x = x.reshape((t_local, sp) + x.shape[1:])
    x = jnp.swapaxes(x, 0, 1).reshape((T,) + x.shape[2:])
    return jnp.moveaxis(x, 0, axis).reshape(shape)


def unstripe_tokens(x, sp, axis=1):
    """Inverse of stripe_tokens."""
    T = x.shape[axis]
    t_local = T // sp
    shape = list(x.shape)
    x = jnp.moveaxis(x, axis, 0)
    x = x.reshape((sp, t_local) + x.shape[1:])
    x = jnp.swapaxes(x, 0, 1).reshape((T,) + x.shape[2:])
    return jnp.moveaxis(x, 0, axis).reshape(shape)


def ring_attention_striped(q, k, v, axis_name, scale=None,
                           use_flash=None):
    """Load-BALANCED causal ring over STRIPED token layout
    (Striped Attention, Brandon et al. 2023; see PAPERS.md pattern
    notes): device s holds tokens {s, s+sp, ...} (stripe_tokens), so
    global causality token i*sp+r >= j*sp+s reduces per block-pair to
    plain causal (i >= j) when r >= s and STRICT causal (i > j) when
    r < s.  Every device computes a ~half-masked block at EVERY ring
    step — wall-clock ~sp * block/2 versus the contiguous ring's
    sp * block (where whichever device holds a fully-visible pair sets
    the pace).  Inputs/outputs are local striped shards inside
    shard_map, like ring_attention."""
    sp = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    gate_ok, bq, bk = _flash_gate_and_blocks(t_local, q.shape[-1],
                                             True)
    if use_flash is None:
        use_flash = gate_ok
    f32 = jnp.float32

    if use_flash:
        from .flash_attention import flash_attention_lse

        def attend(kb, vb, mode):
            o, l = flash_attention_lse(q, kb, vb, mode, scale, bq, bk)
            return o.astype(f32), l
    else:
        from .flash_attention import _reference_lse

        def attend(kb, vb, mode):
            # shares the masked-softmax-with-lse math (incl. the
            # fully-masked-row guards) with the flash fallback
            return _reference_lse(q, kb, vb, mode, scale)

    merge = _merge_lse
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    @jax.checkpoint
    def step(carry, i):
        o_acc, l_acc, kb, vb = carry
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        s = (rank - i) % sp
        # rank >= s: diagonal included; rank < s: strictly causal
        part = jax.lax.cond(rank >= s,
                            lambda kb, vb: attend(kb, vb, True),
                            lambda kb, vb: attend(kb, vb, 'strict'),
                            kb, vb)
        o_acc, l_acc = merge((o_acc, l_acc), part)
        return (o_acc, l_acc, kb, vb), None

    o0, l0 = attend(k, v, True)           # home block: r == s
    (o_acc, l_acc, _, _), _ = jax.lax.scan(
        step, (o0, l0, k, v), jnp.arange(1, sp))
    return o_acc.astype(q.dtype)


def ring_attention_spmd(q, k, v, mesh, causal=True,
                        batch_axes=('dp', 'tp'), seq_axis='sp',
                        use_flash=None, striped=False,
                        pre_striped=False):
    """shard_map wrapper: q/k/v are GLOBAL [B*H, T, D] arrays (traced
    under jit on `mesh`); heads/batch split over `batch_axes`, sequence
    over `seq_axis`; ring rotation rides the `sp` ICI ring.

    `striped=True` (causal only) runs the load-balanced striped ring:
    inputs are striped/unstriped here for drop-in numerics — GSPMD
    inserts the relayout all-to-alls, so pipelines chasing the full 2x
    keep hidden states striped end-to-end and pass `pre_striped=True`
    (inputs already in stripe order; output stays striped)."""
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    spec = P(axes if len(axes) > 1 else (axes[0] if axes else None),
             seq_axis, None)
    if striped and not causal:
        raise ValueError(
            'striped=True requires causal=True: the stripe layout '
            'exists to balance the causal mask; non-causal rings are '
            'already balanced — drop striped.')
    if striped:
        sp = mesh.shape[seq_axis]
        fn = functools.partial(ring_attention_striped,
                               axis_name=seq_axis, use_flash=use_flash)
        if not pre_striped:
            q, k, v = (stripe_tokens(t, sp) for t in (q, k, v))
        out = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)(q, k, v)
        return out if pre_striped else unstripe_tokens(out, sp)
    fn = functools.partial(ring_attention, axis_name=seq_axis,
                           causal=causal, use_flash=use_flash)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
