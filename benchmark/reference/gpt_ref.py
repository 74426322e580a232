"""Plain float32 reference of the GPT-2 architecture that Cerebras-GPT
uses (learned positions, pre-LayerNorm blocks, multi-head causal
attention, tanh-GELU MLP, tied head, shifted cross-entropy), written in
jax.numpy from the published description: no kernel, no cache, no
batching tricks, matmuls at precision 'highest' (on a TPU a float32
matmul otherwise runs in bf16 passes).

It reads the program's parameter dictionary (names as
`model.functional_state()` gives them) and upcasts each tensor where it
is used, one layer at a time, so no float32 copy of a bf16 model is
ever held.  Each piece is one small jitted program, compiled once
(every layer has the same shapes) and kept in jax's persistent cache.

Departures from the published model: the vocabulary's rows are padded
to the program's 50304 and the extra rows take part in the softmax, as
they do in the program.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(F32) + b.astype(F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@jax.jit
def _embed(wte, wpe, ids):
    t = ids.shape[1]
    return wte.astype(F32)[ids] + wpe.astype(F32)[:t][None]


@functools.partial(jax.jit, static_argnames=('heads', 'eps'))
def _block(x, p, *, heads, eps):
    b, t, h = x.shape
    hd = h // heads
    a = _ln(x, p['ln1.weight'], p['ln1.bias'], eps)
    qkv = a @ p['attn.qkv.weight'].astype(F32) \
        + p['attn.qkv.bias'].astype(F32)
    # columns are ordered (q|k|v, head, head_dim)
    qkv = qkv.reshape(b, t, 3, heads, hd)
    q, k, v = (jnp.transpose(qkv[:, :, i], (0, 2, 1, 3))
               for i in range(3))
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    y = jnp.einsum('bhqk,bhkd->bhqd', att, v)
    y = jnp.transpose(y, (0, 2, 1, 3)).reshape(b, t, h)
    x = x + y @ p['attn.proj.weight'].astype(F32) \
        + p['attn.proj.bias'].astype(F32)
    m = _ln(x, p['ln2.weight'], p['ln2.bias'], eps)
    m = _gelu_tanh(m @ p['mlp.fc.weight'].astype(F32)
                   + p['mlp.fc.bias'].astype(F32))
    return x + m @ p['mlp.proj.weight'].astype(F32) \
        + p['mlp.proj.bias'].astype(F32)


@functools.partial(jax.jit, static_argnames=('eps',))
def _logits_at(x, lnw, lnb, wte, positions, *, eps):
    """Logits [B, K, V] at K chosen positions of each row."""
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _ln(picked, lnw, lnb, eps) @ wte.astype(F32).T


@functools.partial(jax.jit, static_argnames=('eps',))
def _row_loss_sum(x_row, ids_row, lnw, lnb, wte, *, eps):
    """Sum over one sequence of -log p(next token)."""
    logits = _ln(x_row[:-1], lnw, lnb, eps) @ wte.astype(F32).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, ids_row[1:, None], axis=1).sum()


def _layer(params, i):
    pre = f'gpt.blocks.{i}.'
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def hidden(params, ids, *, num_layers, num_heads, eps):
    """[B, T] ids -> [B, T, H] float32 states before the final norm."""
    with jax.default_matmul_precision('highest'):
        x = _embed(params['gpt.wte.weight'], params['gpt.wpe.weight'],
                   jnp.asarray(ids, jnp.int32))
        for i in range(num_layers):
            x = _block(x, _layer(params, i), heads=num_heads, eps=eps)
    return x


def logits_at(params, ids, positions, **model):
    """Float32 logits at `positions` [B, K] of right-padded `ids`."""
    x = hidden(params, ids, **model)
    with jax.default_matmul_precision('highest'):
        return _logits_at(
            x, params['gpt.ln_f.weight'], params['gpt.ln_f.bias'],
            params['gpt.wte.weight'], jnp.asarray(positions, jnp.int32),
            eps=model['eps'])


def lm_loss(params, ids, **model):
    """Mean shifted cross-entropy over [B, T] ids, one sequence's
    logits at a time (a [B*T, V] float32 array would not fit beside a
    trainer's state)."""
    ids = jnp.asarray(ids, jnp.int32)
    x = hidden(params, ids, **model)
    total = 0.0
    with jax.default_matmul_precision('highest'):
        for b in range(ids.shape[0]):
            total += float(_row_loss_sum(
                x[b], ids[b], params['gpt.ln_f.weight'],
                params['gpt.ln_f.bias'], params['gpt.wte.weight'],
                eps=model['eps']))
    return total / (ids.shape[0] * (ids.shape[1] - 1))
