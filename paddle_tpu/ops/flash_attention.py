"""FlashAttention for TPU (Pallas).

Reference analogue: the reference's fused attention goes through cuDNN
(paddle/fluid/operators/fused/fmha*); this is the TPU-native equivalent:
an online-softmax tiled kernel that never materialises the [T, T] score
matrix, with a recompute-style Pallas backward (dq / dkv kernels) using
the forward's logsumexp.  SURVEY.md §2 item 36.

Layout: [B*H, T, D] (callers fold batch and heads).  f32 accumulation
regardless of input dtype (bf16 inputs hit the MXU natively).

On non-TPU backends `flash_attention` falls back to a jnp reference
implementation (same math, materialised scores) so tests/CPU runs work.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _gating

__all__ = ['flash_attention', 'flash_attention_lse', 'can_use_pallas',
           'autotune_blocks']

# tuned on v5e at T=4096 D=128: (256, 512) beats XLA's fused einsum
# attention by ~21% (an earlier round; no ledger line holds it)
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

# -- per-shape block tuning --------------------------------------------------
# key "tq,tk,d,causal" -> (bq, bk).  The table is this literal: what a
# checkout runs is what git holds.  tools/tune_flash.py measures
# candidates on the chip and prints the winners; a builder who wants
# one pastes it here.  Explicit block_q/block_k args always win.
_tune_table = {}


def _tuned_blocks(tq, tk, d, causal):
    got = _tune_table.get(f'{tq},{tk},{d},{int(bool(causal))}')
    return got if got else (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)


def autotune_blocks(tq, tk, d, causal=True, dtype=jnp.bfloat16,
                    bh=8, candidates=None, iters=8):
    """Time the kernel per (bq, bk) candidate ON THE LIVE DEVICE and
    record the winner in this process's tuning table (the cuDNN-style
    heuristic table the reference gets from NVIDIA, built empirically
    here).  Returns ((bq, bk), ms)."""
    import time
    import numpy as np

    cands = candidates or [(bq, bk)
                           for bq in (128, 256, 512)
                           for bk in (128, 256, 512, 1024)]
    cands = [(bq, bk) for bq, bk in cands
             if tq % min(bq, tq) == 0 and tk % min(bk, tk) == 0
             and can_use_pallas(tq, tk, d, bq, bk)]
    if not cands:
        return (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K), float('nan')
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(bh, tq, d), dtype)
    k = jnp.asarray(rs.randn(bh, tk, d), dtype)
    v = jnp.asarray(rs.randn(bh, tk, d), dtype)
    scale = 1.0 / math.sqrt(d)
    best, best_ms = None, float('inf')
    for bq, bk in cands:
        bq_, bk_ = min(bq, tq), min(bk, tk)

        # amortize dispatch: chain the kernel in-graph
        @jax.jit
        def run(q, k, v, bq_=bq_, bk_=bk_):
            # chain on Q (output shape == Q shape) so the scan carries
            # a real data dependency between kernel invocations
            def body(c, _):
                return _flash(c, k, v, causal, scale, bq_, bk_), None
            out, _ = jax.lax.scan(body, q, None, length=iters)
            return out

        try:
            float(np.asarray(run(q, k, v)).ravel()[0])   # compile+warm
            t0 = time.perf_counter()
            float(np.asarray(run(q, k, v)).ravel()[0])
            ms = (time.perf_counter() - t0) * 1000 / iters
        except Exception:
            continue
        if ms < best_ms:
            best, best_ms = (bq_, bk_), ms
    if best is None:
        return (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K), float('nan')
    _tune_table[f'{tq},{tk},{d},{int(bool(causal))}'] = best
    return best, best_ms


def _reference_lse(q, k, v, causal, scale):
    s = jnp.einsum('bqd,bkd->bqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool),
                        k=-1 if causal == 'strict' else 0)
        s = jnp.where(mask, s, NEG_INF)
    # masked-softmax that zeroes fully-masked rows (strict mode's row
    # 0) instead of going uniform — matches the Pallas kernels
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e29)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum('bqk,bkd->bqd', p, v.astype(jnp.float32)) \
        / jnp.maximum(l, 1e-30)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return o, lse


def _reference(q, k, v, causal, scale):
    o, _ = _reference_lse(q, k, v, causal, scale)
    return o.astype(q.dtype)


# -- forward kernel ----------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, scale, causal, block_q, block_k,
                num_k_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def compute():
        q = q_ref[0].astype(jnp.float32)                 # [bq, d]
        kb = k_ref[0].astype(jnp.float32)                # [bk, d]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + ki * block_k
            s = jnp.where(rows > cols if causal == 'strict'
                          else rows >= cols, s, NEG_INF)
        m_prev = m_sc[:, :1]                              # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # [bq, bk]
        if causal == 'strict':
            # a fully-masked row (global token 0) has m_new == NEG_INF,
            # making exp(s - m_new) == 1 on masked cells — zero them
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    if causal:
        # skip blocks strictly above the diagonal
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_sc[:, :1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
        lse = (m_sc[:, :1] + jnp.log(safe_l)).astype(jnp.float32)
        # (block_q, 8): narrowest legal tile for per-row scalars
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_pallas(q, k, v, scale, causal, block_q, block_k):
    bh, tq, d = q.shape
    tk = k.shape[1]
    grid = (bh, tq // block_q, tk // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=tk // block_k)
    out, lse = pl.pallas_call(
        kernel,
        name='flash_fwd',
        interpret=_gating.INTERPRET,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )(q, k, v)
    return out, lse


# -- backward kernels --------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc, *, scale, causal, block_q, block_k,
                   num_k_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                           # [bq, 1]
        delta = delta_ref[0][:, :1]                       # [bq, 1]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + ki * block_k
            s = jnp.where(rows > cols if causal == 'strict'
                          else rows >= cols, s, NEG_INF)
        p = jnp.exp(jnp.minimum(s - lse, 0.0))            # [bq, bk]
        if causal == 'strict':
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        ds = p * (dp - delta) * scale
        dq_sc[:] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal,
                    block_q, block_k, num_q_blocks):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + ki * block_k
            s = jnp.where(rows > cols if causal == 'strict'
                          else rows >= cols, s, NEG_INF)
        p = jnp.exp(jnp.minimum(s - lse, 0.0))            # [bq, bk]
        if causal == 'strict':
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dv_sc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, d]
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        ds = p * (dp - delta) * scale
        dk_sc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, d]

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_pallas(res, g, scale, causal, block_q, block_k, g_lse=None):
    q, k, v, out, lse = res
    bh, tq, d = q.shape
    tk = k.shape[1]
    do = g
    # delta_i = rowsum(dO_i * O_i) — f32, broadcast into lane dim 128
    # per-row scalars ride a (bh, tq, 8) layout — the narrowest tile the
    # TPU lowering accepts (vs 128 lanes: 16x less HBM traffic)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    if g_lse is not None:
        # lse cotangent (streaming-merge callers): dlse/ds = p, so the
        # contribution p*g_lse folds into ds = p*(dp - delta) exactly
        # as delta' = delta - g_lse — the kernels stay unchanged
        delta = delta - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[:, :, None], (bh, tq, 8))

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=tk // block_k)
    dq = pl.pallas_call(
        dq_kernel,
        name='flash_bwd_dq',
        interpret=_gating.INTERPRET,
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_q_blocks=tq // block_q)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name='flash_bwd_dkv',
        interpret=_gating.INTERPRET,
        grid=(bh, tk // block_k, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# -- public op ---------------------------------------------------------------

def shapes_tile(tq, tk, d, block_q, block_k):
    """The single shape predicate every Pallas-attention gate shares.
    d=64 compiles fine (Mosaic pads the lane dim); smaller head dims
    waste too much of the tile."""
    bq, bk = min(block_q, tq), min(block_k, tk)
    return (tq % bq == 0 and tk % bk == 0 and d % 64 == 0
            and bq >= 128 and bk >= 128)


def can_use_pallas(tq, tk, d, block_q=DEFAULT_BLOCK_Q,
                   block_k=DEFAULT_BLOCK_K):
    """True iff flash_attention will take the Pallas path for these
    shapes — callers (e.g. GPT attention) use this to choose between
    flash and their own einsum path instead of hitting the slower jnp
    reference fallback."""
    from ._gating import pallas_backend_ok
    return pallas_backend_ok() and shapes_tile(tq, tk, d, block_q,
                                               block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _fwd_pallas(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, res, g):
    return _bwd_pallas(res, g, scale, causal, block_q, block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, scale, block_q, block_k):
    out, lse8 = _fwd_pallas(q, k, v, scale, causal, block_q, block_k)
    return out, lse8[:, :, 0]


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse8 = _fwd_pallas(q, k, v, scale, causal, block_q, block_k)
    return (out, lse8[:, :, 0]), (q, k, v, out, lse8)


def _flash_lse_bwd(causal, scale, block_q, block_k, res, g):
    g_out, g_lse = g
    return _bwd_pallas(res, g_out, scale, causal, block_q, block_k,
                       g_lse=g_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q, k, v, causal, scale, block_q, block_k):
    """Attention returning (out, lse[bh, tq]) for streaming-merge
    callers (ring attention combines per-block partials in (out, lse)
    space).  The lse cotangent is exact: it folds into the shared
    backward kernels as delta' = delta - g_lse (_bwd_pallas), since
    d lse / d s = softmax(s).  Falls back to the jnp reference when
    Pallas is unavailable or the shapes don't tile, like
    flash_attention."""
    from ._gating import pallas_tpu_ok
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    if pallas_tpu_ok() and shapes_tile(q.shape[1], k.shape[1],
                                       q.shape[2], bq, bk):
        return _flash_lse(q, k, v, causal, scale, bq, bk)
    o, lse = _reference_lse(q, k, v, causal, scale)
    return o.astype(q.dtype), lse


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None):
    """Tiled attention over [B*H, T, D] arrays.

    Uses the Pallas kernel on TPU when the sequence lengths divide the
    (>=128) block sizes and D % 64 == 0 (see can_use_pallas); otherwise
    falls back to the jnp reference (identical math, differentiable
    through XLA).  Block sizes resolve per shape from `_tune_table`
    unless given explicitly."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if block_q is None or block_k is None:
        tbq, tbk = _tuned_blocks(q.shape[1], k.shape[1], q.shape[2],
                                 causal)
        block_q = block_q or tbq
        block_k = block_k or tbk
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    if not can_use_pallas(q.shape[1], k.shape[1], q.shape[2], bq, bk):
        return _reference(q, k, v, causal, scale)
    return _flash(q, k, v, causal, scale, bq, bk)


def flash_attention_spmd(q, k, v, mesh, causal=False, scale=None,
                         dp_axis='dp', tp_axis='tp'):
    """Flash attention COMPOSED WITH THE MESH: q/k/v are [B, H, T, D]
    global (GSPMD-traced) arrays; batch shards over dp, heads over tp,
    and each shard runs the Pallas kernel on its local [B/dp * H/tp,
    T, D] slab — attention is head-independent, so no collectives.

    This closes the "single-chip only" gating of round 2: the einsum
    attention XLA partitions automatically, but the flash kernel needs
    this explicit shard_map to ride a hybrid mesh.
    """
    from jax.sharding import PartitionSpec as P
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    shape = dict(mesh.shape)
    dp = shape.get(dp_axis, 1)
    tp = shape.get(tp_axis, 1)
    spec = P(dp_axis if dp > 1 else None, tp_axis if tp > 1 else None,
             None, None)

    # resolve blocks from the tuning table against the GLOBAL T (the
    # per-shard T is the same — only batch/heads shard)
    T_, D_ = q.shape[2], q.shape[3]
    bq, bk = _tuned_blocks(T_, k.shape[2], D_, causal)
    bq, bk = min(bq, T_), min(bk, k.shape[2])

    def local(qv, kv, vv):
        B, H, T, D = qv.shape
        # call the KERNEL directly: the caller already gated via
        # can_use_pallas_spmd, and flash_attention's own gate would see
        # the installed global mesh and silently fall back to the slow
        # reference inside every shard (r3 review finding)
        o = _flash(qv.reshape(B * H, T, D),
                   kv.reshape(B * H, kv.shape[2], D),
                   vv.reshape(B * H, vv.shape[2], D),
                   causal, scale, bq, bk)
        return o.reshape(B, H, T, D)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def can_use_pallas_spmd(B, H, T, d, mesh, dp_axis='dp', tp_axis='tp'):
    """Gate for flash_attention_spmd: pallas available (mesh allowed),
    batch/heads divide the mesh axes, and the LOCAL shapes tile."""
    from ._gating import pallas_tpu_ok
    if mesh is None or not pallas_tpu_ok():
        return False
    shape = dict(mesh.shape)
    dp = shape.get(dp_axis, 1)
    tp = shape.get(tp_axis, 1)
    # other model-parallel axes must not shard attention inputs
    if shape.get('sp', 1) > 1 or shape.get('pp', 1) > 1:
        return False
    if B % dp or H % tp:
        return False
    return shapes_tile(T, T, d, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
