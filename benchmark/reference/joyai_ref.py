"""Plain float32 reference of the JoyAI-LLM-Flash decoder
(huggingface.co/jdopensource/JoyAI-LLM-Flash config.json, `model_type:
joyai_llm_flash`: the DeepSeek-V3 block), written in jax.numpy from the
layer equations the config fixes, in the EXPANDED form only (every head's
keys and values from the latent; never the absorbed form the program's
decode steps run, so that the absorption is what gets checked):

    x0     = embed[ids]                                  # not scaled
    h      = RMSNorm_1(x)
    q      = W_qb RMSNorm_q(W_qa h)                      # [T, H, 128 + 64]
    q_nope, q_pe = q[..., :128], rope(q[..., 128:])      # pairs (2i, 2i+1)
    [c ; k_pe] = W_kva h;  c = RMSNorm_kv(c);  k_pe = rope(k_pe)
    [k_nope ; v] = W_kvb c                               # [T, H, 128 + 128]
    a      = causal softmax((q_nope k_nope^T + q_pe k_pe^T) / sqrt(192)) v
    x1     = x + W_o a
    h2     = RMSNorm_2(x1)
    dense layer:   y = W_down (silu(W_gate h2) * (W_up h2))
    routed layer:  s = sigmoid(h2 W_r)                   # all experts
                   top = the k largest of (s + b)        # b: choice only
                   w   = scale * s[top] / (sum s[top] + 1e-20)
                   y   = shared(h2) + sum_{e in top, e held} w_e expert_e(h2)
    x2     = x1 + y
    logits = RMSNorm_final(x) W_head                     # untied

The experts a layer holds are the configuration's share (`held_experts`
[first, first + count) of `num_experts`), as the program's: what the
others would add is left out here too, and that partial result goes on
to the next layer.  No kernel, no cache, no batching, no sorting: every
held expert multiplies every row, one after another (a scan), and the
rows it was not chosen for are weighted 0.  Matmuls at precision
'highest'.  It imports nothing from paddle_tpu.

`weights(config, seed)` draws the benchmark's own weights, a tensor at a
time, on the device, in the dtype the configuration serves (norms round
1 and the router's bias round 0, N(0, 0.02) in float32, so that a
program that dropped one, or put it in the wrong place, can fail).
Attention runs one head and one block of queries at a time so that a
row of 18,432 positions fits beside the engine; its pieces are jitted a
shape, so a caller who hands every pass ids padded to ONE length
compiles them once a process.

`weights_as` (the control): every matrix rounded to that dtype before
it is used, in a call of its own that hands the jitted piece a tensor
IN that dtype (a cast down and up inside one jitted function is removed
by XLA on the TPU).

Departures from the published model: none in the mathematics the
configuration's `assumed` lists; the weights are random from the seed;
only the configuration's layers and held experts exist (its cut); the
multi-token-prediction module is not built (it does not reach the
next-token logits).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
QUERY_BLOCK = 1024
MLP = ('gate_proj.weight', 'up_proj.weight', 'down_proj.weight')


# -- the benchmark's weights --------------------------------------------------------
def shapes(m):
    """{name: shape} in the program's names (`functional_state()`)."""
    h, H = m['hidden_size'], m['num_heads']
    nope, rope, dv = (m['qk_nope_head_dim'], m['qk_rope_head_dim'],
                      m['v_head_dim'])
    r, f = m['kv_lora_rank'], m['intermediate_size']
    E, held = m['num_experts'], m['held_experts'][1]

    def mlp(prefix, width):
        return {prefix + 'gate_proj.weight': (h, width),
                prefix + 'up_proj.weight': (h, width),
                prefix + 'down_proj.weight': (width, h)}

    attention = {'input_norm.weight': (h,),
                 'attn.q_a_proj.weight': (h, m['q_lora_rank']),
                 'attn.q_a_norm.weight': (m['q_lora_rank'],),
                 'attn.q_b_proj.weight': (m['q_lora_rank'],
                                          H * (nope + rope)),
                 'attn.kv_a_proj.weight': (h, r + rope),
                 'attn.kv_a_norm.weight': (r,),
                 'attn.kv_b_proj.weight': (r, H * (nope + dv)),
                 'attn.o_proj.weight': (H * dv, h),
                 'post_attn_norm.weight': (h,)}
    routed = {'router.weight': (h, E), 'router.bias': (E,),
              'experts.gate_proj': (held, h, f),
              'experts.up_proj': (held, h, f),
              'experts.down_proj': (held, f, h),
              **mlp('shared.', m['num_shared_experts'] * f)}
    out = {'model.embed.weight': (m['vocab_size'], h)}
    for i in range(m['num_layers']):
        layer = {**attention,
                 **(mlp('mlp.', m['dense_intermediate_size'])
                    if i < m['num_dense_layers'] else routed)}
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


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _matmul(x, w):
    return x @ w.astype(F32)


@jax.jit
def _mlp(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
        @ wd.astype(F32)


def _rope(x, theta):
    """x [T, H, d], position t = row index; pairs (2i, 2i + 1) rotated
    in place by the angle t * theta^(-2i/d)."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=('heads', 'nope', 'theta',
                                             'eps'))
def _attention(q, ckv, kv_norm, w_kvb, *, heads, nope, theta, eps):
    """One sequence, expanded.  q [T, H (nope + rope)], ckv [T, latent
    + rope] (W_kva h), w_kvb [latent, H (nope + v)] -> [T, H v].  One
    head and one block of queries at a time."""
    t = q.shape[0]
    rope = q.shape[1] // heads - nope
    r = ckv.shape[1] - rope
    q = q.reshape(t, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], theta)
    c = _rms(ckv[:, :r], kv_norm, eps)
    k_pe = _rope(ckv[:, None, r:], theta)[:, 0]               # [T, rope]
    kv = (c @ w_kvb.astype(F32)).reshape(t, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    dv = v.shape[-1]
    scale = (nope + rope) ** -0.5
    rows = jnp.arange(t)
    pad = -t % QUERY_BLOCK
    q_nope = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0)))
    q_pe = jnp.pad(q_pe, ((0, pad), (0, 0), (0, 0)))
    rows = jnp.pad(rows, (0, pad))
    cols = jnp.arange(t)

    def block(x):
        qn, qp, rb, kn, vj = x       # [Q, n], [Q, rope], [Q], [T, n], [T, dv]
        s = (qn @ kn.T + qp @ k_pe.T) * scale
        seen = cols[None, :] <= rb[:, None]
        return jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1) @ vj

    def head(x):
        qn, qp, kn, vj = x
        nb = qn.shape[0] // QUERY_BLOCK
        y = jax.lax.map(lambda b: block((b[0], b[1], b[2], kn, vj)),
                        (qn.reshape(nb, QUERY_BLOCK, -1),
                         qp.reshape(nb, QUERY_BLOCK, -1),
                         rows.reshape(nb, QUERY_BLOCK)))
        return y.reshape(-1, dv)

    y = jax.lax.map(head, (jnp.moveaxis(q_nope, 1, 0),
                           jnp.moveaxis(q_pe, 1, 0),
                           jnp.moveaxis(k_nope, 1, 0),
                           jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(y, 0, 1).reshape(-1, heads * dv)[:t]


@functools.partial(jax.jit, static_argnames=('k', 'scale', 'first',
                                             'count'))
def _route(logits, bias, *, k, scale, first, count):
    """[T, E] -> the weights each HELD expert gets a row [T, count]:
    the sigmoid scores of the k experts chosen over all E by score +
    bias, renormalised over the k and scaled; 0 elsewhere."""
    s = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(s + bias, k)
    rows = jnp.arange(logits.shape[0])[:, None]
    top_s = s[rows, top_i]
    w = scale * top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    mix = jnp.zeros_like(logits).at[rows, top_i].set(w)
    return mix[:, first:first + count]


@jax.jit
def _experts(h, mix, wg, wu, wd):
    """sum_e mix[:, e] W_down,e (silu(W_gate,e h) * (W_up,e h)) over the
    held experts, one after another."""
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


def routed_part(params, i, h, *, model, weights_as=None):
    """The routed layer `i`'s held experts' part of its output for the
    normed rows `h` [T, hidden], and the router's logits: the share
    test's piece."""
    p = _layer(params, i)
    logits = _matmul(h, _low(p['router.weight'], weights_as))
    first, count = model['held_experts']
    mix = _route(logits, p['router.bias'], k=model['experts_per_token'],
                 scale=float(model['route_scale']), first=int(first),
                 count=int(count))
    return _experts(h, mix, *(_low(p[f'experts.{n}_proj'], weights_as)
                              for n in ('gate', 'up', 'down'))), logits


def shared_part(params, i, h, weights_as=None):
    p = _layer(params, i)
    return _mlp(h, *(_low(p['shared.' + n], weights_as) for n in MLP))


def _step(params, i, x, *, model, weights_as):
    """Layer `i` over one sequence: x [T, H] -> (x, what the layer
    computed on the way: the attention heads' output [T, H v] before
    W_o, and in a routed layer the router's logits [T, E] and the
    routed-plus-shared output [T, H])."""
    eps = model['rms_norm_eps']

    def mm(x, w):
        return _matmul(x, _low(w, weights_as))

    p = _layer(params, i)
    h = _norm(x, p['input_norm.weight'], eps=eps)
    q = mm(_norm(mm(h, p['attn.q_a_proj.weight']),
                 p['attn.q_a_norm.weight'], eps=eps),
           p['attn.q_b_proj.weight'])
    a = _attention(q, mm(h, p['attn.kv_a_proj.weight']),
                   p['attn.kv_a_norm.weight'],
                   _low(p['attn.kv_b_proj.weight'], weights_as),
                   heads=model['num_heads'], nope=model['qk_nope_head_dim'],
                   theta=float(model['rope_theta']), eps=eps)
    x = x + mm(a, p['attn.o_proj.weight'])
    h = _norm(x, p['post_attn_norm.weight'], eps=eps)
    taps = {'attn': a}
    if i < model['num_dense_layers']:
        y = _mlp(h, *(_low(p['mlp.' + n], weights_as) for n in MLP))
    else:
        y, logits = routed_part(params, i, h, model=model,
                                weights_as=weights_as)
        y = y + shared_part(params, i, h, weights_as)
        taps.update(router=logits, moe=y)
    # dispatch runs ahead of the device, and what a layer allocates is
    # held until it has run: wait a layer, hold one layer's
    y = jax.block_until_ready(y)
    return x + y, taps


def _embedded(params, ids_row, weights_as):
    table = _low(params['model.embed.weight'], weights_as)
    return table[jnp.asarray(ids_row, jnp.int32)].astype(F32)


def hidden(params, ids_row, *, model, weights_as=None):
    """[T] ids of one sequence -> [T, H] float32 states before the
    final norm."""
    x = _embedded(params, ids_row, weights_as)
    for i in range(model['num_layers']):
        x, _taps = _step(params, i, x, model=model, weights_as=weights_as)
    return x


def taps_at(params, ids_row, layers, rows, *, model, weights_as=None):
    """{layer: {'attn', and in a routed layer 'router' and 'moe'}} at
    the positions `rows` [n] of `ids_row` (right-padded to any length:
    what follows a position cannot reach it): what the decode steps
    that fed those ids have to compute in those layers."""
    out = {}
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision('highest'):
        x = _embedded(params, ids_row, weights_as)
        for i in range(max(layers) + 1):
            x, taps = _step(params, i, x, model=model,
                            weights_as=weights_as)
            if i in layers:
                out[i] = {k: v[rows] for k, v in taps.items()}
    return out


def chosen(logits, bias, k):
    """The experts [T, k] a router's `logits` [T, E] and `bias` [E]
    choose over all E, sorted."""
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
