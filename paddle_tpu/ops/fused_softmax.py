"""Fused (optionally masked) softmax (Pallas forward, y-reusing backward).

Reference analogue: softmax_op.cu / fused softmax-with-mask kernels in
the reference; one VMEM pass on TPU.  SURVEY.md §2 item 36.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _gating

__all__ = ['fused_softmax']

_BLOCK_ROWS = 256


def _reference(x, mask):
    xf = x.astype(jnp.float32)
    if mask is not None:
        xf = xf + mask.astype(jnp.float32)
    return jax.nn.softmax(xf, axis=-1).astype(x.dtype)


def _kernel(x_ref, y_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    y_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _masked_kernel(x_ref, mask_ref, y_ref):
    x = x_ref[:].astype(jnp.float32) + mask_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    y_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _fwd_pallas(x2d, mask2d, block_rows):
    n, h = x2d.shape
    grid = (n // block_rows,)
    if mask2d is None:
        return pl.pallas_call(
            _kernel,
            name='softmax_fwd',
            interpret=_gating.INTERPRET,
            grid=grid,
            in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
        )(x2d)
    return pl.pallas_call(
        _masked_kernel,
        name='masked_softmax_fwd',
        interpret=_gating.INTERPRET,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
    )(x2d, mask2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sm(x2d, mask2d, block_rows):
    return _fwd_pallas(x2d, mask2d, block_rows)


def _sm_fwd(x2d, mask2d, block_rows):
    y = _fwd_pallas(x2d, mask2d, block_rows)
    return y, (y, mask2d is not None)


def _sm_bwd(block_rows, res, g):
    (y, had_mask) = res
    yf = y.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    dx = yf * (gf - jnp.sum(gf * yf, axis=-1, keepdims=True))
    dx = dx.astype(y.dtype)
    # d/dmask of softmax(x + mask) equals d/dx
    return dx, (dx if had_mask else None)


_sm.defvjp(_sm_fwd, _sm_bwd)


def fused_softmax(x, mask=None, block_rows=_BLOCK_ROWS):
    """Softmax over the last axis (+ optional additive mask);
    Pallas-fused on TPU, jnp fallback elsewhere and for rows too long
    for VMEM (the 50k-vocabulary softmax)."""
    h = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= s
    from ._gating import pallas_backend_ok, pick_block_rows
    br = pick_block_rows(n, block_rows, h)
    if not (pallas_backend_ok() and h % 128 == 0 and br):
        return _reference(x, mask)
    m2d = None
    if mask is not None:
        m2d = jnp.broadcast_to(mask, x.shape).reshape(n, h)
    return _sm(x.reshape(n, h), m2d, br).reshape(x.shape)
