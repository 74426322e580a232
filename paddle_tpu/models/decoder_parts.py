"""The ordinary parts of today's decoder block, kept apart from any one
model so that the next decoder of this block (an attention model with
grouped-query heads, say) takes them as they are: RMSNorm, rotary
positions, bias-free projections onto grouped query and key/value
heads with per-head q/k norms, a gated SiLU MLP.

Each part is an `nn.Layer` that owns its Parameters, drawn by the
repo's initialisers in the dtype asked for (a 4 B-parameter model is
never built in float32 first), and a pure function of a parameter
dictionary (`functional_state()` names, the part's prefix cut off)
that the serving modules trace.  The Layer's `forward` runs the same
function eagerly.

Precision: weights are held in the model's dtype (bfloat16 when
served); a matmul rounds its activation to the weight's dtype on the
way in and accumulates and returns float32, and everything between
matmuls (the residual stream, norms, rotary, the gate) stays float32.
At 16 rows a token step the activations are no traffic beside the
weights, and a residual stream rounded to bfloat16 at every add was
the larger half of the engine's distance from the float32 reference
(PERF.md section 6, PR 27).
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply as _apply
from ..nn import initializer as init

__all__ = ['Dense', 'RMSNorm', 'GatedMLP', 'GroupedProjections',
           'matmul', 'rms_norm', 'rotary', 'gated_mlp', 'project_heads', 'sub']

F32 = jnp.float32


def sub(params, prefix):
    """The entries of `params` under `prefix`, the prefix cut off."""
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def matmul(x, w):
    """x W with x rounded to W's dtype and a float32 result."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=F32)


def rms_norm(x, weight, eps):
    """x / rms(x) * weight over the last axis, computed in float32 and
    returned in x's dtype."""
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * weight.astype(F32)).astype(x.dtype)


def rotary(x, positions, theta, interleaved=False):
    """Rotary positions in float32.  x [B, T, H, d], positions [B, T]
    (absolute).  The rotate-half form pairs lane i with i + d/2;
    `interleaved` pairs lanes (2i, 2i + 1) and rotates them in place
    (a config's `rope_interleave`), frequency i either way."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[..., None] * inv             # [B,T,d/2]
    x = x.astype(F32)
    if interleaved:
        cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                         -1).reshape(x.shape)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def gated_mlp(p, x):
    """down( silu(gate x) * (up x) )."""
    a = matmul(x, p['gate_proj.weight'])
    b = matmul(x, p['up_proj.weight'])
    return matmul(jax.nn.silu(a) * b, p['down_proj.weight'])


def project_heads(p, x, positions, *, num_heads, num_kv_heads, head_dim,
                  eps, theta):
    """x [B, T, h] -> q [B,T,Hq,d] and k [B,T,Hkv,d] (normed over the
    head dimension with their own weights, then rotated where
    `positions` is given: a layer that carries no positions hands
    None) and v [B,T,Hkv,d], all float32."""
    B, T, _ = x.shape
    q = matmul(x, p['q_proj.weight']).reshape(B, T, num_heads, head_dim)
    k = matmul(x, p['k_proj.weight']).reshape(B, T, num_kv_heads,
                                              head_dim)
    v = matmul(x, p['v_proj.weight']).reshape(B, T, num_kv_heads,
                                              head_dim)
    q = rms_norm(q, p['q_norm.weight'], eps)
    k = rms_norm(k, p['k_norm.weight'], eps)
    if positions is not None:
        q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    return q, k, v


class Dense(nn.Layer):
    """y = x W, no bias; W [in, out] drawn N(0, std) in `dtype`."""

    def __init__(self, in_features, out_features, *, std, dtype):
        super().__init__()
        self.weight = self.create_parameter(
            (in_features, out_features), dtype=dtype,
            default_initializer=init.Normal(0.0, std))

    def forward(self, x):
        return _apply(matmul, x, self.weight, op_name='dense')


class RMSNorm(nn.Layer):
    def __init__(self, dim, *, eps, dtype):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter(
            (dim,), dtype=dtype, default_initializer=init.Constant(1.0))

    def forward(self, x):
        return _apply(lambda a, w: rms_norm(a, w, self.eps), x,
                      self.weight, op_name='rms_norm')


class GatedMLP(nn.Layer):
    def __init__(self, hidden_size, intermediate_size, *, std, dtype):
        super().__init__()
        kw = dict(std=std, dtype=dtype)
        self.gate_proj = Dense(hidden_size, intermediate_size, **kw)
        self.up_proj = Dense(hidden_size, intermediate_size, **kw)
        self.down_proj = Dense(intermediate_size, hidden_size, **kw)

    def forward(self, x):
        names = ('gate_proj.weight', 'up_proj.weight', 'down_proj.weight')
        return _apply(
            lambda a, *w: gated_mlp(dict(zip(names, w)), a), x,
            self.gate_proj.weight, self.up_proj.weight,
            self.down_proj.weight, op_name='gated_mlp')


class GroupedProjections(nn.Layer):
    """q on `num_heads` heads, k and v on `num_kv_heads`, the output
    projection back, and the q/k norms' weights."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim, *,
                 eps, std, dtype):
        super().__init__()
        kw = dict(std=std, dtype=dtype)
        self.q_proj = Dense(hidden_size, num_heads * head_dim, **kw)
        self.k_proj = Dense(hidden_size, num_kv_heads * head_dim, **kw)
        self.v_proj = Dense(hidden_size, num_kv_heads * head_dim, **kw)
        self.o_proj = Dense(num_heads * head_dim, hidden_size, **kw)
        self.q_norm = RMSNorm(head_dim, eps=eps, dtype=dtype)
        self.k_norm = RMSNorm(head_dim, eps=eps, dtype=dtype)
