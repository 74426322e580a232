"""Plain float32 reference of the Trinity-Mini decoder
(huggingface.co/arcee-ai/Trinity-Mini config.json, `model_type: afmoe`;
the block published as `transformers/models/afmoe/modeling_afmoe.py`),
written in jax.numpy from the layer equations of ISSUE 35:

    x0  = embed[ids] * sqrt(hidden)                      # mup_enabled
    h   = RMSNorm_in(x)
    q   = RMSNorm_q(heads(h Wq))  k = RMSNorm_k(heads(h Wk))  v = heads(h Wv)
    window layer:  q, k = rotary(q), rotary(k)           # full: NO positions
    a   = causal softmax(q k^T / sqrt(d)) v              # window: i - j < window
    a   = a * sigmoid(h Wg)                              # before Wo
    x1  = x + RMSNorm_post_attn(a Wo)
    h2  = RMSNorm_pre_mlp(x1)
    dense layer:   y = Wdown (silu(Wgate h2) * (Wup h2))
    routed layer:  s   = sigmoid(h2 Wr)
                   top = the k largest of (s + b)        # b: choice only
                   w   = route_scale * s[top] / (sum s[top] + 1e-20)
                   y   = shared(h2) + sum_{e in top} w_e expert_e(h2)
    x2  = x1 + RMSNorm_post_mlp(y)
    logits = RMSNorm_final(x) Whead                      # head untied

No kernel, no cache, no batching, no sorting: every expert multiplies
every row, one expert after another (a scan: one expert's three
matrices in float32 at a time, so a layer's 3.2 GB never stand beside
the engine), and the rows it was not chosen for are weighted 0.
Matmuls at precision 'highest'.  It imports nothing from paddle_tpu.

`weights(config, seed)` draws the benchmark's own weights, a tensor at
a time, on the device, in the dtype the configuration serves
(`initializer_range`; norms round 1 and the router's bias round 0,
N(0, 0.02) in float32: a tensor that is all 0 or all 1 would let a
program that dropped it, or put it in the wrong place, pass).  The
runner loads each into the program through `set_state_dict` as it is
drawn and the reference reads that dictionary, never what the program
holds.  It works one sequence, one layer, one key/value head and one
block of queries at a time, and waits a layer.  Its pieces are jitted a
shape: a caller that hands every pass ids padded to ONE length (the
runner does: the engine's `max_model_len`) compiles them once a
process; on the chip a new length costs a minute of compiling, a pass
at the longest length a second or two.

`weights_as` (the control): every matrix rounded to that dtype before
it is used, the precision below the configuration's, in a call of its
own that hands the jitted piece a tensor IN that dtype (`_low`: a cast
down and up inside one jitted function is removed by XLA on the TPU).

Departures from the published model: none in the mathematics the
configuration's `assumed` lists; the weights are random from the seed,
and only the configuration's layers exist (its cut).
"""
import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
QUERY_BLOCK = 1024
MLP = ('gate_proj.weight', 'up_proj.weight', 'down_proj.weight')


# -- the benchmark's weights --------------------------------------------------------
def shapes(m):
    """{name: shape} in the program's names (`functional_state()`)."""
    h, d = m['hidden_size'], m['head_dim']
    E, f = m['num_experts'], m['intermediate_size']
    hq, hkv = m['num_heads'] * d, m['num_kv_heads'] * d

    def mlp(prefix, width):
        return {prefix + 'gate_proj.weight': (h, width),
                prefix + 'up_proj.weight': (h, width),
                prefix + 'down_proj.weight': (width, h)}

    attention = {'input_norm.weight': (h,),
                 'attn.q_proj.weight': (h, hq),
                 'attn.k_proj.weight': (h, hkv),
                 'attn.v_proj.weight': (h, hkv),
                 'attn.o_proj.weight': (hq, h),
                 'attn.q_norm.weight': (d,), 'attn.k_norm.weight': (d,),
                 'attn.gate_proj.weight': (h, hq),
                 'post_attn_norm.weight': (h,),
                 'pre_mlp_norm.weight': (h,)}
    routed = {'router.weight': (h, E), 'router.bias': (E,),
              'experts.gate_proj': (E, h, f), 'experts.up_proj': (E, h, f),
              'experts.down_proj': (E, f, h),
              **mlp('shared.', m['num_shared_experts'] * f)}
    out = {'model.embed.weight': (m['vocab_size'], h)}
    for i in range(m['num_layers']):
        layer = {**attention,
                 **(mlp('mlp.', m['dense_intermediate_size'])
                    if i < m['num_dense_layers'] else routed),
                 'post_mlp_norm.weight': (h,)}
        out.update({f'model.layers.{i}.{k}': s for k, s in layer.items()})
    out.update({'model.norm.weight': (h,),
                'lm_head.weight': (m['vocab_size'], h)})
    return out


@functools.partial(jax.jit, static_argnames=('shape', 'dtype', 'std',
                                             'norm'))
def _draw(key, *, shape, dtype, std, norm):
    x = std * jax.random.normal(key, shape, F32)
    return (1.0 + x if norm else x).astype(dtype)


def weights(config, seed):
    """(name, tensor) of every tensor of the model, one at a time."""
    m = config['model']
    key = jax.random.key(jnp.uint32(int(seed) % 2 ** 32))
    for n, (name, shape) in enumerate(shapes(m).items()):
        bias = name.endswith('router.bias')
        yield name, _draw(
            jax.random.fold_in(key, n), shape=shape,
            dtype='float32' if bias else config['weights_dtype'],
            std=0.02 if bias else m['initializer_range'],
            norm=name.endswith('norm.weight'))


# -- the layer ------------------------------------------------------------------------
def _low(w, weights_as):
    """`w` as the jitted pieces take it: in `weights_as` where that is
    given and `w` is a matrix (norms and the bias stay as they are)."""
    if weights_as is not None and w.ndim >= 2:
        return w.astype(weights_as)
    return w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(F32)


@functools.partial(jax.jit, static_argnames=('scale',))
def _embed(table, ids, *, scale):
    return table[ids].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _matmul(x, w):
    return x @ w.astype(F32)


@jax.jit
def _gate(a, g):
    return a * jax.nn.sigmoid(g)


@jax.jit
def _mlp(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
        @ wd.astype(F32)


def _rope(x, theta):
    """x [T, H, d], position t = row index; the rotate-half form."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=(
    'heads', 'kv_heads', 'theta', 'eps', 'window'))
def _attention(q, k, v, q_norm, k_norm, *, heads, kv_heads, theta, eps,
               window):
    """One sequence.  q [T, Hq d], k, v [T, Hkv d] -> [T, Hq d].  q
    and k are normed over a head and, in a window layer, rotated.  One
    key/value head with its group of query heads, and one block of
    queries, at a time."""
    t = q.shape[0]
    d = q.shape[1] // heads
    group = heads // kv_heads
    q = _rms(q.reshape(t, heads, d), q_norm, eps)
    k = _rms(k.reshape(t, kv_heads, d), k_norm, eps)
    if window is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    v = v.reshape(t, kv_heads, d)
    rows = jnp.arange(t)
    pad = -t % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    rows = jnp.pad(rows, (0, pad))
    cols = jnp.arange(t)

    def block(x):
        qb, rb, kj, vj = x          # [Q, G, d], [Q], [T, d], [T, d]
        s = jnp.einsum('qgd,ld->gql', qb, kj) / jnp.sqrt(F32(d))
        seen = cols[None, :] <= rb[:, None]
        if window is not None:
            # `window` keys, the query's own among them
            seen = seen & (rb[:, None] - cols[None, :] < window)
        a = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
        return jnp.einsum('gql,ld->qgd', a, vj)

    def head(x):
        qj, kj, vj = x              # [Tq, G, d], [T, d], [T, d]
        nb = qj.shape[0] // QUERY_BLOCK
        y = jax.lax.map(
            lambda b: block((b[0], b[1], kj, vj)),
            (qj.reshape(nb, QUERY_BLOCK, group, d),
             rows.reshape(nb, QUERY_BLOCK)))
        return y.reshape(-1, group, d)

    y = jax.lax.map(head, (
        jnp.moveaxis(q.reshape(-1, kv_heads, group, d), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(y, 0, 1).reshape(-1, heads * d)[:t]


@functools.partial(jax.jit, static_argnames=('k', 'scale'))
def _route(logits, bias, *, k, scale):
    """[T, E] -> the weights every expert gets a row [T, E]: the
    sigmoid scores of the k experts chosen by score + bias,
    renormalised and scaled; 0 elsewhere."""
    s = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(s + bias, k)
    rows = jnp.arange(logits.shape[0])[:, None]
    top_s = s[rows, top_i]
    w = scale * top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(logits).at[rows, top_i].set(w)


@jax.jit
def _experts(h, mix, wg, wu, wd):
    """sum_e mix[:, e] W_down,e (silu(W_gate,e h) * (W_up,e h)): every
    expert over every row, one expert after another."""
    def one(y, x):
        g, u, dn, m = x
        a = jax.nn.silu(h @ g.astype(F32)) * (h @ u.astype(F32))
        return y + m[:, None] * (a @ dn.astype(F32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (wg, wu, wd, mix.T))
    return y


def _layer(params, i):
    pre = f'model.layers.{i}.'
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def _step(params, i, x, *, model, weights_as):
    """Layer `i` over one sequence: x [T, H] -> (x [T, H], what the
    layer computed on the way: the router's logits [T, E] (None in a
    dense layer), the gated attention output [T, Hq d] before the
    output projection, the MLP's or the routed-plus-shared output
    [T, H] before its norm)."""
    eps = model['rms_norm_eps']

    def mm(x, w):
        return _matmul(x, _low(w, weights_as))

    def mlp(h, prefix):
        return _mlp(h, *(_low(p[prefix + n], weights_as) for n in MLP))

    p = _layer(params, i)
    h = _norm(x, p['input_norm.weight'], eps=eps)
    a = _attention(
        mm(h, p['attn.q_proj.weight']), mm(h, p['attn.k_proj.weight']),
        mm(h, p['attn.v_proj.weight']), p['attn.q_norm.weight'],
        p['attn.k_norm.weight'], heads=model['num_heads'],
        kv_heads=model['num_kv_heads'], theta=float(model['rope_theta']),
        eps=eps,
        window=model['window'] if model['window_layout'][i] else None)
    a = _gate(a, mm(h, p['attn.gate_proj.weight']))
    x = x + _norm(mm(a, p['attn.o_proj.weight']),
                  p['post_attn_norm.weight'], eps=eps)
    h = _norm(x, p['pre_mlp_norm.weight'], eps=eps)
    if i < model['num_dense_layers']:
        logits, y = None, mlp(h, 'mlp.')
    else:
        logits = mm(h, p['router.weight'])
        mix = _route(logits, p['router.bias'],
                     k=model['experts_per_token'],
                     scale=float(model['route_scale']))
        y = _experts(h, mix, *(_low(p[f'experts.{n}_proj'], weights_as)
                               for n in ('gate', 'up', 'down'))) \
            + mlp(h, 'shared.')
    # dispatch runs ahead of the device, and what a layer allocates is
    # held until it has run: wait a layer, hold one layer's
    y = jax.block_until_ready(y)
    return (x + _norm(y, p['post_mlp_norm.weight'], eps=eps),
            {'router': logits, 'attn': a, 'moe': y})


def _embedded(params, ids_row, model, weights_as):
    return _embed(_low(params['model.embed.weight'], weights_as),
                  jnp.asarray(ids_row, jnp.int32),
                  scale=math.sqrt(model['hidden_size']))


def hidden(params, ids_row, *, model, weights_as=None):
    """[T] ids of one sequence -> [T, H] float32 states before the
    final norm."""
    x = _embedded(params, ids_row, model, weights_as)
    for i in range(model['num_layers']):
        x, _taps = _step(params, i, x, model=model, weights_as=weights_as)
    return x


def taps_at(params, ids_row, layers, rows, *, model, weights_as=None):
    """{layer: {'router': logits [n, E], 'attn': the gated attention
    output [n, Hq d] before the output projection, 'moe': the routed
    plus the shared experts' output [n, H]}} at the positions `rows`
    [n] of `ids_row` (right-padded to any length: what follows a
    position cannot reach it): what the decode steps that fed those
    ids have to compute in those (routed) layers."""
    out = {}
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision('highest'):
        x = _embedded(params, ids_row, model, weights_as)
        for i in range(max(layers) + 1):
            x, taps = _step(params, i, x, model=model,
                            weights_as=weights_as)
            if i in layers:
                out[i] = {k: v[rows] for k, v in taps.items()}
    return out


def chosen(logits, bias, k):
    """The experts [T, k] a router's `logits` [T, E] and `bias` [E]
    choose, sorted."""
    score = jax.nn.sigmoid(jnp.asarray(logits, F32)) + jnp.asarray(bias)
    return jnp.sort(jax.lax.top_k(score, k)[1], -1)


@jax.jit
def _head(x, rows):
    return x @ rows.astype(F32).T


def logits_at(params, ids, positions, weights_as=None, *, model):
    """Float32 logits [B, K, V] at `positions` [B, K] of right-padded
    `ids` [B, T] (what follows a position cannot reach it)."""
    out = []
    with jax.default_matmul_precision('highest'):
        for row, pos in zip(ids, positions):
            x = hidden(params, row, model=model, weights_as=weights_as)
            x = _norm(x[jnp.asarray(pos, jnp.int32)],
                      params['model.norm.weight'],
                      eps=model['rms_norm_eps'])
            out.append(_head(x, _low(params['lm_head.weight'],
                                     weights_as)))
    return jnp.stack(out)
