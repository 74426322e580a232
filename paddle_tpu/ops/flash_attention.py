"""FlashAttention for TPU (Pallas).

Reference analogue: the reference's fused attention goes through cuDNN
(paddle/fluid/operators/fused/fmha*); this is the TPU-native equivalent:
an online-softmax tiled kernel that never materialises the [T, T] score
matrix, with a recompute-style Pallas backward (dq / dkv kernels) using
the forward's logsumexp.  SURVEY.md §2 item 36.

Layout: [B*H, T, D] (callers fold batch and heads).  Every matmul
takes its operands in the dtype q, k, v and do are stored in (p and ds
are cast to it) and accumulates in float32; scores, max, sum, lse,
delta and the accumulators are float32.  On the v5e this is what the
MXU did anyway: Mosaic's default for float32 operands is one bfloat16
pass, and a bfloat16 caller's outputs and gradients are bit for bit
those of the float32-cast kernel (PERF.md section 6, PR 32).

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

# What every shape without a table entry runs.  The one sweep a ledger
# line stands behind (PR 32, a v5e, [64, 2048, 128] bfloat16 causal,
# forward plus backward) read 5.55 ms a call here; fewer, larger tiles
# are faster there (the table below), no other shape has been swept.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

# -- per-shape block tuning --------------------------------------------------
# key "tq,tk,d,causal" -> (bq, bk).  The table is this literal: what a
# checkout runs is what git holds.  tools/tune_flash.py measures
# candidates on the chip and prints every one; a builder who wants an
# entry pastes it here.  Explicit block_q/block_k args always win.
_tune_table = {
    # the benchmark's train_seq2048: 4.09 ms a call against 5.55 at
    # (256, 512), twelve candidates (PERF.md section 6, PR 32)
    '2048,2048,128,1': (512, 1024),
}


def _tuned_blocks(tq, tk, d, causal):
    got = _tune_table.get(f'{tq},{tk},{d},{int(bool(causal))}')
    return got if got else (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)


def autotune_blocks(tq, tk, d, causal=True, dtype=jnp.bfloat16,
                    bh=8, candidates=None, iters=8, report=None):
    """Time forward plus backward (flash_fwd, flash_bwd_dq,
    flash_bwd_dkv, on operands of `dtype`) per (bq, bk) candidate ON
    THE LIVE DEVICE and record the winner in this process's tuning
    table (the cuDNN-style heuristic table the reference gets from
    NVIDIA, built empirically here).  `report((bq, bk), ms)` is called
    for every candidate that compiled.  Returns ((bq, bk), ms)."""
    import time
    import numpy as np

    cands = candidates or [(bq, bk)
                           for bq in (128, 256, 512)
                           for bk in (128, 256, 512, 1024)]
    cands = sorted({(min(bq, tq), min(bk, tk)) for bq, bk in cands})
    cands = [(bq, bk) for bq, bk in cands
             if tq % bq == 0 and tk % bk == 0
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
        # amortize dispatch: chain the kernels in-graph
        @jax.jit
        def run(q, k, v):
            def loss(q, k, v):
                out = _flash(q, k, v, causal, scale, bq, bk)
                return jnp.sum(out.astype(jnp.float32))

            # chain on the gradients (shapes == operands' shapes) so
            # the scan carries a real data dependency from each
            # iteration's three kernels into the next's
            def body(c, _):
                g = jax.grad(loss, argnums=(0, 1, 2))(*c)
                return tuple((x + 1e-3 * gx).astype(x.dtype)
                             for x, gx in zip(c, g)), None
            out, _ = jax.lax.scan(body, (q, k, v), None, length=iters)
            return out

        try:
            jax.block_until_ready(run(q, k, v))          # compile+warm
            t0 = time.perf_counter()
            jax.block_until_ready(run(q, k, v))
            ms = (time.perf_counter() - t0) * 1000 / iters
        except Exception:
            continue
        if report is not None:
            report((bq, bk), ms)
        if ms < best_ms:
            best, best_ms = (bq, bk), ms
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


# -- what the three kernels share -------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def _dot(a, b, dims):
    """The MXU gets the operands in the dtype they are stored in and
    accumulates in float32.  Float32 operands take the caller's matmul
    precision, as they always did; a bfloat16 product is exact in
    float32, so one pass is all there is, and Mosaic refuses a higher
    precision on such operands ("Bad lhs type") if the caller's
    jax_default_matmul_precision reaches it."""
    precision = (None if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _last_k_block(qi, block_q, block_k):
    """The last block column a causal row of query blocks computes."""
    return (qi * block_q + block_q - 1) // block_k


def _first_q_block(ki, block_q, block_k, num_q_blocks):
    """The first block row a causal column of key blocks computes; a
    column past the last query row (tk > tq) computes none and gets
    the last."""
    return jnp.minimum((ki * block_k) // block_q, num_q_blocks - 1)


def _kv_index_map(causal, block_q, block_k):
    """K/V block of grid step (b, qi, ki) of flash_fwd and
    flash_bwd_dq: a step above the diagonal names the block the step
    before it held, so the pipeline fetches nothing for it."""
    if not causal:
        return lambda b, qi, ki: (b, ki, 0)
    return lambda b, qi, ki: (
        b, jnp.minimum(ki, _last_k_block(qi, block_q, block_k)), 0)


def _q_index_map(causal, block_q, block_k, num_q_blocks):
    """q/do/lse/delta block of flash_bwd_dkv's grid step (b, ki, qi):
    the steps above the diagonal come first in a column and name the
    first block that column computes."""
    if not causal:
        return lambda b, ki, qi: (b, qi, 0)
    return lambda b, ki, qi: (
        b, jnp.maximum(qi, _first_q_block(ki, block_q, block_k,
                                          num_q_blocks)), 0)


def _for_tile(causal, qi, ki, block_q, block_k, compute):
    """Run compute() for tile (qi, ki) unless it lies wholly above the
    causal diagonal."""
    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(compute)
    else:
        compute()


def _scores(q_ref, k_ref, scale, causal, qi, ki):
    """The [bq, bk] float32 score tile, masked cells at NEG_INF."""
    s = _dot(q_ref[0], k_ref[0], _NT) * scale
    if causal:
        block_q, block_k = s.shape
        rows = jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) + qi * block_q
        cols = jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) + ki * block_k
        s = jnp.where(rows > cols if causal == 'strict'
                      else rows >= cols, s, NEG_INF)
    return s


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
        s = _scores(q_ref, k_ref, scale, causal, qi, ki)
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
        vb = v_ref[0]
        acc_sc[:] = acc_sc[:] * alpha + _dot(p.astype(vb.dtype), vb, _NN)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    _for_tile(causal, qi, ki, block_q, block_k, compute)

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
    kv_map = _kv_index_map(causal, block_q, block_k)
    out, lse = pl.pallas_call(
        kernel,
        name='flash_fwd',
        interpret=_gating.INTERPRET,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
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

def _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, scale, causal,
          qi, ki):
    """The tile's probabilities p and ds / scale = p * (dp - delta),
    float32 [bq, bk]; ds's scale multiplies the accumulated dq and dk
    once, at _finalize."""
    s = _scores(q_ref, k_ref, scale, causal, qi, ki)
    p = jnp.exp(jnp.minimum(s - lse_ref[0][:, :1], 0.0))
    if causal == 'strict':
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    dp = _dot(do_ref[0], v_ref[0], _NT)
    return p, p * (dp - delta_ref[0][:, :1])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc, *, scale, causal, block_q, block_k,
                   num_k_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def compute():
        _, ds = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      scale, causal, qi, ki)
        kb = k_ref[0]
        dq_sc[:] += _dot(ds.astype(kb.dtype), kb, _NN)

    _for_tile(causal, qi, ki, block_q, block_k, compute)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[:] * scale).astype(dq_ref.dtype)


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
        p, ds = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      scale, causal, qi, ki)
        q, do = q_ref[0], do_ref[0]
        dv_sc[:] += _dot(p.astype(do.dtype), do, _TN)     # [bk, d]
        dk_sc[:] += _dot(ds.astype(q.dtype), q, _TN)      # [bk, d]

    _for_tile(causal, qi, ki, block_q, block_k, compute)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = (dk_sc[:] * scale).astype(dk_ref.dtype)
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
    kv_map = _kv_index_map(causal, block_q, block_k)
    dq = pl.pallas_call(
        dq_kernel,
        name='flash_bwd_dq',
        interpret=_gating.INTERPRET,
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
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
    q_map = _q_index_map(causal, block_q, block_k, tq // block_q)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name='flash_bwd_dkv',
        interpret=_gating.INTERPRET,
        grid=(bh, tk // block_k, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, 8), q_map),
            pl.BlockSpec((1, block_q, 8), q_map),
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
    waste too much of the tile.

    What a causal grid does with the tiles this admits: the grid is
    the full [tq / bq, tk / bk] rectangle; a tile wholly above the
    diagonal runs no body (_for_tile) and, since its index maps name
    the block its neighbour on the diagonal's side holds
    (_kv_index_map, _q_index_map), fetches nothing; every other tile
    runs ONE body that builds the mask.  A second, mask-free body for
    tiles wholly below the diagonal was measured and dropped: the
    tile-wide elementwise work hides under the matmuls and the
    per-row work (PERF.md section 6, PR 32)."""
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
