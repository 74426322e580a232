"""Plain float32 reference of the GPT-2 architecture that Cerebras-GPT
uses (learned positions, pre-LayerNorm blocks, multi-head causal
attention, tanh-GELU MLP, tied head, shifted cross-entropy), written in
jax.numpy from the published description: no kernel, no cache, no
batching tricks, matmuls at precision 'highest' (on a TPU a float32
matmul otherwise runs in bf16 passes).

It reads a parameter dictionary under the names the program's
`set_state_dict` loads, and upcasts each tensor where it is used, one
layer at a time, so no float32 copy of a bf16 model is ever held.  Each
piece is one small jitted program, compiled once (every layer has the
same shapes) and kept in jax's persistent cache.

`weights` draws that dictionary from a seed, by the source's own
initialisation (Cerebras-GPT `config.json`: `initializer_range` 0.02,
the residual projections by 1/sqrt(2 L) as GPT-2 does), in one jitted
call on the device: the serve runner loads it into the program and
hands the reference this copy, never what the program holds.

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


def shapes(*, vocab_size, hidden_size, num_layers, intermediate_size,
           max_seq_len):
    """{name: shape} of every tensor of the model, from its sizes."""
    h, f = hidden_size, intermediate_size
    block = {'ln1.weight': (h,), 'ln1.bias': (h,),
             'attn.qkv.weight': (h, 3 * h), 'attn.qkv.bias': (3 * h,),
             'attn.proj.weight': (h, h), 'attn.proj.bias': (h,),
             'ln2.weight': (h,), 'ln2.bias': (h,),
             'mlp.fc.weight': (h, f), 'mlp.fc.bias': (f,),
             'mlp.proj.weight': (f, h), 'mlp.proj.bias': (h,)}
    out = {'gpt.wte.weight': (vocab_size, h),
           'gpt.wpe.weight': (max_seq_len, h)}
    for i in range(num_layers):
        out.update({f'gpt.blocks.{i}.{k}': s for k, s in block.items()})
    out.update({'gpt.ln_f.weight': (h,), 'gpt.ln_f.bias': (h,)})
    return out


@functools.partial(jax.jit, static_argnames=('sizes', 'dtype', 'std'))
def _draw(seed, *, sizes, dtype, std):
    sizes = dict(sizes)
    layers = sizes['num_layers']
    key = jax.random.key(seed)
    out, stacks = {}, {}
    for n, (name, shape) in enumerate(shapes(**sizes).items()):
        layered = name.startswith('gpt.blocks.')
        kind = name.split('.', 3)[-1] if layered else name
        if kind not in stacks:
            # one draw a kind of tensor, all layers' at once
            x = std * jax.random.normal(
                jax.random.fold_in(key, n),
                (layers,) + shape if layered else shape, F32)
            if kind.endswith('proj.weight'):
                x = x / (2.0 * layers) ** 0.5
            if kind.endswith(('ln1.weight', 'ln2.weight', 'ln_f.weight')):
                x = 1.0 + x
            stacks[kind] = x.astype(dtype)
        out[name] = stacks[kind][int(name.split('.')[2])] if layered \
            else stacks[kind]
    return out


def weights(seed, dtype, std=0.02, **sizes):
    """Every tensor of the model from `seed` ({name: array} on the
    device, in `dtype`): normal with deviation `std`, the residual
    projections' by 1/sqrt(2 L) smaller.  Biases and the norms' shifts
    are drawn too and the norms' scales round 1, where the source
    starts them at 0 and 1: a tensor that is all 0 or all 1 would let a
    program that dropped it pass."""
    return _draw(jnp.uint32(int(seed) % 2 ** 32),
                 sizes=tuple(sorted(sizes.items())), dtype=dtype, std=std)


def _layer(params, i):
    pre = f'gpt.blocks.{i}.'
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def _rounded(p, weights_as):
    """The control's weights: every matrix rounded to `weights_as` and
    back (a tensor at a time, so no second copy of the model is held);
    biases and norms as they are."""
    if weights_as is None:
        return p
    if isinstance(p, dict):
        return {k: _rounded(v, weights_as) for k, v in p.items()}
    return p.astype(weights_as).astype(p.dtype) if p.ndim == 2 else p


def hidden(params, ids, *, num_layers, num_heads, eps, weights_as=None):
    """[B, T] ids -> [B, T, H] float32 states before the final norm."""
    with jax.default_matmul_precision('highest'):
        x = _embed(_rounded(params['gpt.wte.weight'], weights_as),
                   _rounded(params['gpt.wpe.weight'], weights_as),
                   jnp.asarray(ids, jnp.int32))
        for i in range(num_layers):
            x = _block(x, _rounded(_layer(params, i), weights_as),
                       heads=num_heads, eps=eps)
    return x


def logits_at(params, ids, positions, weights_as=None, **model):
    """Float32 logits at `positions` [B, K] of right-padded `ids`.
    `weights_as` (a dtype below the configuration's, say
    'float8_e4m3fn') makes this the control of the serve runners'
    `correct`: the same reference in the nearest lower precision."""
    x = hidden(params, ids, weights_as=weights_as, **model)
    with jax.default_matmul_precision('highest'):
        return _logits_at(
            x, params['gpt.ln_f.weight'], params['gpt.ln_f.bias'],
            _rounded(params['gpt.wte.weight'], weights_as),
            jnp.asarray(positions, jnp.int32), eps=model['eps'])


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
