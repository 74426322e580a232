"""Fused LayerNorm (Pallas forward, stats-reusing backward).

Reference analogue: the reference's layer_norm CUDA kernel
(paddle/fluid/operators/layer_norm_op.cu); here the forward is one
Pallas pass (mean/rstd in f32, normalize+affine fused) and the backward
reuses the saved stats through XLA.  SURVEY.md §2 item 36.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _gating

__all__ = ['fused_layer_norm']

_BLOCK_ROWS = 256


def _reference(x, gamma, beta, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(x.dtype)


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                      # [rows, H]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    y = y * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = jnp.broadcast_to(mean, mean_ref.shape)
    rstd_ref[:] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _fwd_pallas(x2d, gamma, beta, eps, block_rows):
    n, h = x2d.shape
    grid = (n // block_rows,)
    kernel = functools.partial(_fwd_kernel, eps=eps)
    y, mean, rstd = pl.pallas_call(
        kernel,
        name='layer_norm_fwd',
        interpret=_gating.INTERPRET,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
        ],
    )(x2d, gamma, beta)
    return y, mean[:, 0], rstd[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x2d, gamma, beta, eps, block_rows):
    y, _, _ = _fwd_pallas(x2d, gamma, beta, eps, block_rows)
    return y


def _ln_fwd(x2d, gamma, beta, eps, block_rows):
    y, mean, rstd = _fwd_pallas(x2d, gamma, beta, eps, block_rows)
    return y, (x2d, gamma, mean, rstd)


def _ln_bwd(eps, block_rows, res, g):
    x2d, gamma, mean, rstd = res
    xf = x2d.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xhat = (xf - mean[:, None]) * rstd[:, None]
    dy = gf * gamma.astype(jnp.float32)
    h = x2d.shape[-1]
    dx = (dy - jnp.mean(dy, axis=-1, keepdims=True)
          - xhat * jnp.mean(dy * xhat, axis=-1, keepdims=True)) \
        * rstd[:, None]
    dgamma = jnp.sum(gf * xhat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(gf, axis=0)
    return dx.astype(x2d.dtype), dgamma, dbeta.astype(gamma.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(x, gamma=None, beta=None, eps=1e-5,
                     block_rows=_BLOCK_ROWS):
    """LayerNorm over the last axis; Pallas-fused on TPU."""
    h = x.shape[-1]
    lead = x.shape[:-1]
    n = 1
    for s in lead:
        n *= s
    from ._gating import pallas_backend_ok, pick_block_rows
    br = pick_block_rows(n, block_rows, h)
    if not (pallas_backend_ok() and gamma is not None
            and beta is not None and h % 128 == 0 and br):
        return _reference(x, gamma, beta, eps)
    y = _ln(x.reshape(n, h), gamma, beta, eps, br)
    return y.reshape(x.shape)
