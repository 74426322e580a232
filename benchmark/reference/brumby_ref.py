"""Plain float32 reference of the Brumby-14B-Base decoder
(huggingface.co/manifestai/Brumby-14B-Base config.json; power
retention, arXiv:2507.04239), written in jax.numpy from the layer
equations of ISSUE 27, section 1: RMSNorm, grouped-query projections
without biases, q/k norms over the head dimension, rotary positions,
power retention of degree 2 in its FIRST form (the masked square:
`a[t,l] = exp(b_t - b_l) (s q_t.k_l)^2`, `y_t = sum_l a v_l / (sum_l a
+ eps_r)`), gated SiLU MLP, untied head.  No state, no chunking, no
feature map, no kernel, no cache; matmuls at precision 'highest' (on a
TPU a float32 matmul otherwise runs in bf16 passes).  It imports
nothing from paddle_tpu.  `state_readout` is the same first form at a
sequence's last position with given vectors in the query's place: what
the recurrence's state has to hold, for the probe's second limit.

It reads the program's parameter dictionary (names as
`model.functional_state()` gives them) and upcasts one tensor at a
time inside the matmul that uses it (the compiler fuses the convert:
no float32 copy of a weight is ever held), one layer, one sequence and
one key/value head's [T, T] square at a time: 4.2 B float32 parameters
do not fit beside the engine, and the allocator's peak with the
reference has to stay the engine's own.  Each piece is one small jitted
program, compiled once a shape.

Departures from the published model: none in the mathematics the
configuration's `assumed` lists; the weights are random from the seed,
and only the first `num_layers` layers exist (the configuration's cut).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS_R = 1e-6


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(F32)


@jax.jit
def _matmul(x, w):
    return x @ w.astype(F32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(x, w, *, eps):
    return _rms(x, w, eps)


def _rope(x, theta):
    """x [T, H, d], position t = row index; the rotate-half form."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=('heads', 'kv_heads', 'eps',
                                             'theta'))
def _retention(q, k, v, graw, qn, kn, *, heads, kv_heads, eps, theta):
    """One sequence.  q [T, Hq d], k, v [T, Hkv d], graw [T, Hkv] ->
    [T, Hq d].  One key/value head and its group of query heads after
    another: the heads share nothing, and the [T, T] squares of all 40
    at once are what would not fit beside the engine."""
    t = q.shape[0]
    d = q.shape[1] // heads
    group = heads // kv_heads
    q = _rope(_rms(q.reshape(t, heads, d), qn, eps), theta)
    k = _rope(_rms(k.reshape(t, kv_heads, d), kn, eps), theta)
    v = v.reshape(t, kv_heads, d)
    b = jnp.cumsum(jax.nn.log_sigmoid(graw), axis=0)   # [T, Hkv]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(x):
        qj, kj, vj, bj = x              # [T, G, d], [T, d], [T, d], [T]
        sc = jnp.einsum('tgd,ld->gtl', qj, kj) / jnp.sqrt(F32(d))
        diff = bj[:, None] - bj[None, :]               # b_t - b_l
        a = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)),
                      0.0) * sc * sc
        return jnp.einsum('gtl,ld->tgd', a, vj) \
            / (a.sum(-1).T[:, :, None] + EPS_R)

    y = jax.lax.map(head, (
        jnp.moveaxis(q.reshape(t, kv_heads, group, d), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), b.T))
    return jnp.moveaxis(y, 0, 1).reshape(t, heads * d)


@jax.jit
def state_readout(k, v, g, r):
    """What a sequence's state holds at its end, as section 1 defines
    it, read by vectors `r` without ever being built: for each of them
    the first form's weights at the last position with `r` where the
    query stands (s left out), `a_l = exp(b_T - b_l) (r . k_l)^2`, and
    `num = sum_l a_l v_l` (= phi(r)^T S_T), `den = sum_l a_l`
    (= phi(r)^T z_T).  k (normed, rotated), v [T, Hkv, d], g [T, Hkv]
    the log decays, r [M, d] -> num [Hkv, M, d], den [Hkv, M]."""
    with jax.default_matmul_precision('highest'):
        k, v, g, r = (x.astype(F32) for x in (k, v, g, r))
        # b_T - b_l summed from the end: b reaches -1000 over a prompt,
        # where float32 keeps 6e-5, and the difference is wanted to
        # 1e-6 where it is small (the positions the state remembers)
        left = jnp.cumsum(g[::-1], axis=0)[::-1] - g
        sc = jnp.einsum('md,lhd->hml', r, k)
        a = jnp.exp(left).T[:, None, :] * sc * sc       # [Hkv, M, T]
        return jnp.einsum('hml,lhd->hmd', a, v), a.sum(-1)


@jax.jit
def _gated(a, b):
    return jax.nn.silu(a) * b


def _layer(params, i):
    pre = f'model.layers.{i}.'
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def hidden(params, ids_row, *, num_layers, num_heads, num_kv_heads, eps,
           theta):
    """[T] ids of one sequence -> [T, H] float32 states before the
    final norm."""
    x = _embed(params['model.embed.weight'],
               jnp.asarray(ids_row, jnp.int32))
    for i in range(num_layers):
        p = _layer(params, i)
        h = _norm(x, p['input_norm.weight'], eps=eps)
        y = _retention(
            _matmul(h, p['attn.q_proj.weight']),
            _matmul(h, p['attn.k_proj.weight']),
            _matmul(h, p['attn.v_proj.weight']),
            _matmul(h, p['attn.g_proj.weight']),
            p['attn.q_norm.weight'], p['attn.k_norm.weight'],
            heads=num_heads, kv_heads=num_kv_heads, eps=eps,
            theta=float(theta))
        x = x + _matmul(y, p['attn.o_proj.weight'])
        h = _norm(x, p['post_norm.weight'], eps=eps)
        m = _gated(_matmul(h, p['mlp.gate_proj.weight']),
                   _matmul(h, p['mlp.up_proj.weight']))
        # dispatch runs ahead of the device, and what a layer allocates
        # is held until it has run: wait a layer, hold one layer's
        x = jax.block_until_ready(x + _matmul(m, p['mlp.down_proj.weight']))
    return x


@jax.jit
def _head(x, rows):
    return x @ rows.astype(F32).T


def logits_at(params, ids, positions, **model):
    """Float32 logits [B, K, V] at `positions` [B, K] of right-padded
    `ids` [B, T] (what follows a position cannot reach it)."""
    eps = model['eps']
    head = params['lm_head.weight']
    out = []
    with jax.default_matmul_precision('highest'):
        for row, pos in zip(ids, positions):
            x = hidden(params, row, **model)
            x = _norm(x[jnp.asarray(pos, jnp.int32)],
                      params['model.norm.weight'], eps=eps)
            out.append(_head(x, head))
    return jnp.stack(out)
