"""Plain float32 reference of the Granite-4.0-H-Micro decoder
(huggingface.co/ibm-granite/granite-4.0-h-micro config.json,
`model_type: granitemoehybrid` with no routed experts), written in
jax.numpy from the layer equations the config fixes:

    x0 = embedding_multiplier * embed[ids]
    layer l:  x = x + m * mixer_l(RMSNorm_in(x))          # m: residual_multiplier
              h = RMSNorm_post(x);  [g | u] = h W_in
              x = x + m * (silu(g) * u) W_out
    logits = RMSNorm_f(x) embed^T / logits_scaling          # tied

    attention mixer (layer_types 'attention', NO positions):
              q, k, v = h Wq, h Wk, h Wv   (query head i reads kv head i // G)
              y = causal softmax(attention_multiplier q k^T) v;  W_o y
    Mamba-2 mixer:  [z | xBC | dt] = h W_in_proj
              xBC = silu(causal depthwise conv_k(xBC) + b);  [x | B | C] = xBC
              dt = softplus(dt + dt_bias);  A = -exp(A_log)
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (a head: S [P, N])
              y_t = S_t C_t + D x_t
              W_out_proj RMSNorm(y * silu(z))   (over all of d_inner)

The Mamba layers run the SEQUENTIAL recurrence above, one position
after another (`lax.scan`), never a chunked form: the program's chunked
prefill and its one-token decode are both checked against the
definition.  No kernel, no cache, no batching.  Matmuls at precision
'highest'.  `state_readout`, the definition of a held state, runs in
float64 on the host.  It imports nothing from paddle_tpu.

`weights(config, seed)` draws the benchmark's own weights, a tensor at
a time, in the program's names: matrices N(0, s) and norms 1 + N(0, s)
(s the configuration's `initializer_range`, 0.02) in the dtype the
configuration serves; the conv's taps and bias
U(-1/2, 1/2) (the default of a depthwise conv of 4 taps), and the
heads' scalars as Mamba-2 initialises them, in float32: A_log = log(1
.. heads), dt_bias the inverse softplus of a dt drawn log-uniform in
[0.001, 0.1], D = 1.  So the heads remember from a few positions to
thousands, as a trained model's do (with N(0, 0.02) everywhere every
head would halve its state a token and a state check would see
nothing).

The pieces are jitted a shape and take a layer's weights upcast in the
call, so a caller who hands every pass ids padded to ONE length
compiles them once a process and holds one layer's float32 weights at
a time beside the engine.  Attention runs one head and one block of
queries at a time; the tied head runs a block of the vocabulary at a
time.

`weights_as` (the control): every matrix rounded to that dtype before
it is used, in a call of its own that hands the jitted piece a tensor
IN that dtype.

Departures from the published model: none in the mathematics; the
weights are random from the seed.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG = -1e30
QUERY_BLOCK = 1024
VOCAB_BLOCKS = 8


# -- the benchmark's weights --------------------------------------------------------
def layer_kinds(m):
    return tuple(m['layer_types'])


def shapes(m):
    """{name: shape} in the program's names (`functional_state()`)."""
    h = m['hidden_size']
    H, P, N = m['mamba_n_heads'], m['mamba_d_head'], m['mamba_d_state']
    inner = H * P
    conv = inner + 2 * N
    hq, hkv, d = m['num_heads'], m['num_kv_heads'], m['head_dim']
    mamba = {'mamba.in_proj.weight': (h, inner + conv + H),
             'mamba.conv_weight': (m['mamba_d_conv'], conv),
             'mamba.conv_bias': (conv,),
             'mamba.dt_bias': (H,), 'mamba.A_log': (H,), 'mamba.D': (H,),
             'mamba.norm.weight': (inner,),
             'mamba.out_proj.weight': (inner, h)}
    attention = {'attn.q_proj.weight': (h, hq * d),
                 'attn.k_proj.weight': (h, hkv * d),
                 'attn.v_proj.weight': (h, hkv * d),
                 'attn.o_proj.weight': (hq * d, h)}
    common = {'input_norm.weight': (h,), 'post_norm.weight': (h,),
              'mlp.input_linear.weight': (h, 2 * m['intermediate_size']),
              'mlp.output_linear.weight': (m['intermediate_size'], h)}
    out = {'model.embed.weight': (m['vocab_size'], h)}
    for i, kind in enumerate(layer_kinds(m)):
        layer = {**common, **(mamba if kind == 'mamba' else attention)}
        out.update({f'model.layers.{i}.{k}': s for k, s in layer.items()})
    out['model.norm.weight'] = (h,)
    return out


@functools.partial(jax.jit, static_argnames=('shape', 'dtype', 'kind',
                                             'std'))
def _draw(key, *, shape, dtype, kind, std):
    if kind == 'uniform':
        x = jax.random.uniform(key, shape, F32, -0.5, 0.5)
    elif kind == 'A_log':
        x = jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))
    elif kind == 'dt_bias':
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == 'D':
        x = jnp.ones(shape, F32)
    else:
        x = std * jax.random.normal(key, shape, F32)
        if kind == 'norm':
            x = 1.0 + x
    return x.astype(dtype)


def weights(config, seed):
    """(name, tensor) of every tensor of the model, one at a time."""
    m = config['model']
    key = jax.random.key(jnp.uint32(int(seed) % 2 ** 32))
    for n, (name, shape) in enumerate(shapes(m).items()):
        last = name.rsplit('.', 1)[-1]
        if last in ('dt_bias', 'A_log', 'D'):
            kind, dtype = last, 'float32'
        elif last in ('conv_weight', 'conv_bias'):
            kind, dtype = 'uniform', config['weights_dtype']
        else:
            kind = 'norm' if name.endswith('norm.weight') else 'normal'
            dtype = config['weights_dtype']
        yield name, _draw(jax.random.fold_in(key, n), shape=shape,
                          dtype=dtype, kind=kind,
                          std=float(m['initializer_range']))


# -- the pieces ------------------------------------------------------------------------
def _low(w, weights_as):
    """`w` as the jitted pieces take it: in `weights_as` where that is
    given and `w` is a matrix (the callers hand the matrices alone:
    norms, the conv and the scalars stay as they are)."""
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
def _mlp(h, w_in, w_out):
    gu = h @ w_in.astype(F32)
    width = w_out.shape[0]
    return (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ w_out.astype(F32)


@functools.partial(jax.jit, static_argnames=('heads', 'scale'))
def _attention(q, k, v, *, heads, scale):
    """One sequence: q [T, Hq d], k, v [T, Hkv d] -> [T, Hq d]; causal,
    `scale` times q.k, query head i reading key/value head i // G.  One
    head and one block of queries at a time."""
    t = q.shape[0]
    kv_heads = k.shape[1] // (q.shape[1] // heads)
    d = q.shape[1] // heads
    q = q.reshape(t, heads, d)
    group = heads // kv_heads
    k = jnp.repeat(k.reshape(t, kv_heads, d), group, axis=1)
    v = jnp.repeat(v.reshape(t, kv_heads, d), group, axis=1)
    pad = -t % QUERY_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    rows = jnp.pad(jnp.arange(t), (0, pad))
    cols = jnp.arange(t)

    def head(x):
        qh, kh, vh = x
        nb = qh.shape[0] // QUERY_BLOCK

        def block(b):
            qb, rb = b
            s = scale * (qb @ kh.T)
            seen = cols[None, :] <= rb[:, None]
            return jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1) @ vh

        y = jax.lax.map(block, (qh.reshape(nb, QUERY_BLOCK, d),
                                rows.reshape(nb, QUERY_BLOCK)))
        return y.reshape(-1, d)

    y = jax.lax.map(head, (jnp.moveaxis(qp, 1, 0), jnp.moveaxis(k, 1, 0),
                           jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(y, 0, 1)[:t].reshape(t, heads * d)


def recurrence(x, dt, A, B, C):
    """The definition, a position at a time from the empty state: x [T,
    H, P], dt [T, H], A [H], B and C [T, N].  Returns y [T, H, P] and
    the state [H, P, N] after the last position."""
    H, P = x.shape[1:]

    def step(S, v):
        x_t, dt_t, B_t, C_t = v
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[..., None] * B_t[None, None, :]
        return S, jnp.einsum('hpn,n->hp', S, C_t)

    S, y = jax.lax.scan(step, jnp.zeros((H, P, B.shape[1]), F32),
                        (x, dt, B, C))
    return y, S


def _float32_part(proj, conv_w, conv_b, dt_bias, A_log, *, heads, d_state):
    """What a Mamba mixer computes in float32 of `proj` [T, conv +
    heads], in_proj's output of the conv's channels and of dt: x [T,
    H, P], dt through its softplus, A, B and C."""
    conv = proj.shape[1] - heads
    inner = conv - 2 * d_state
    xbc, dt = proj[:, :conv], jax.nn.softplus(proj[:, conv:] + dt_bias)
    K, t = conv_w.shape[0], proj.shape[0]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(conv_w[k].astype(F32) * xp[k:k + t]
                          for k in range(K)) + conv_b.astype(F32))
    return (xbc[:, :inner].reshape(t, heads, inner // heads), dt,
            -jnp.exp(A_log), xbc[:, inner:inner + d_state],
            xbc[:, inner + d_state:])


@functools.partial(jax.jit, static_argnames=('heads', 'd_state', 'eps'))
def _mamba(h, w_in, conv_w, conv_b, dt_bias, A_log, D, norm_w, *, heads,
           d_state, eps):
    """One sequence h [T, hidden] -> the mixer's output before out_proj
    [T, d_inner]."""
    inner = norm_w.shape[0]
    zxbcdt = h @ w_in.astype(F32)
    x, dt, A, B, C = _float32_part(zxbcdt[:, inner:], conv_w, conv_b,
                                   dt_bias, A_log, heads=heads,
                                   d_state=d_state)
    y, _ = recurrence(x, dt, A, B, C)
    y = (y + D[:, None] * x).reshape(x.shape[0], inner)
    return _rms(y * jax.nn.silu(zxbcdt[:, :inner]), norm_w, eps)


def _layer(params, i):
    pre = f'model.layers.{i}.'
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def _mixer(p, h, kind, model, weights_as):
    """The mixer of one layer over one sequence's normed rows: its
    output before the output projection, and that projection."""
    if kind == 'mamba':
        m = {k[len('mamba.'):]: v for k, v in p.items()
             if k.startswith('mamba.')}
        y = _mamba(h, _low(m['in_proj.weight'], weights_as),
                   m['conv_weight'], m['conv_bias'], m['dt_bias'],
                   m['A_log'], m['D'], m['norm.weight'],
                   heads=model['mamba_n_heads'],
                   d_state=model['mamba_d_state'],
                   eps=model['rms_norm_eps'])
        return y, _matmul(y, _low(m['out_proj.weight'], weights_as))
    q, k, v = (_matmul(h, _low(p[f'attn.{n}_proj.weight'], weights_as))
               for n in 'qkv')
    y = _attention(q, k, v, heads=model['num_heads'],
                   scale=float(model['attention_multiplier']))
    return y, _matmul(y, _low(p['attn.o_proj.weight'], weights_as))


def _step(params, i, x, *, model, weights_as):
    """Layer `i` over one sequence: x [T, hidden] -> (x, the mixer's
    output before its projection)."""
    eps, m = model['rms_norm_eps'], float(model['residual_multiplier'])
    p = _layer(params, i)
    h = _norm(x, p['input_norm.weight'], eps=eps)
    tap, y = _mixer(p, h, layer_kinds(model)[i], model, weights_as)
    x = x + m * y
    h = _norm(x, p['post_norm.weight'], eps=eps)
    y = _mlp(h, _low(p['mlp.input_linear.weight'], weights_as),
             _low(p['mlp.output_linear.weight'], weights_as))
    # dispatch runs ahead of the device, and what a layer allocates is
    # held until it has run: wait a layer, hold one layer's
    x = jax.block_until_ready(x + m * y)
    return x, tap


def _embedded(params, ids_row, model, weights_as):
    table = _low(params['model.embed.weight'], weights_as)
    return table[jnp.asarray(ids_row, jnp.int32)].astype(F32) \
        * float(model['embedding_multiplier'])


def hidden(params, ids_row, *, model, weights_as=None):
    """[T] ids of one sequence -> [T, hidden] float32 states before the
    final norm."""
    x = _embedded(params, ids_row, model, weights_as)
    for i in range(len(layer_kinds(model))):
        x, _tap = _step(params, i, x, model=model, weights_as=weights_as)
    return x


def taps_at(params, ids_row, layers, rows, *, model, weights_as=None):
    """{layer: {'mamba' or 'attn': [n, ...]}}: a Mamba layer's mixer
    output before out_proj, an attention layer's heads' output before
    W_o, at the positions `rows` [n] of `ids_row` (right-padded to any
    length: what follows a position cannot reach it)."""
    out = {}
    rows = jnp.asarray(rows, jnp.int32)
    kinds = layer_kinds(model)
    with jax.default_matmul_precision('highest'):
        x = _embedded(params, ids_row, model, weights_as)
        for i in range(max(layers) + 1):
            x, tap = _step(params, i, x, model=model,
                           weights_as=weights_as)
            if i in layers:
                name = 'mamba' if kinds[i] == 'mamba' else 'attn'
                out[i] = {name: tap[rows]}
    return out


def state_readout(proj, length, layer, r, *, model):
    """What the definition's state of a Mamba layer holds after the
    first `length` positions of `proj` [T, conv_dim + heads] (in_proj's
    output of the conv's channels and of dt, before the conv and the
    softplus), the layer's `mamba.` tensors `layer` ({'conv_weight',
    'conv_bias', 'dt_bias', 'A_log'}), read by the rows of r [m, N]:
    [H, P, m].  In float64 on the host: the conv, the softplus and the
    sequential recurrence, a position at a time, of the state read by
    r (S_t r = exp(dt_t A) S_{t-1} r + dt_t x_t (B_t r), the same
    recurrence: the read-out is linear), so that no rounding of a
    device's float32 stands between the definition and a state it
    judges."""
    f64 = np.float64
    heads, N = int(model['mamba_n_heads']), int(model['mamba_d_state'])
    proj = np.asarray(proj, f64)[:int(length)]
    w, b, dt_bias, A_log = (np.asarray(jnp.asarray(layer[k], F32), f64)
                            for k in ('conv_weight', 'conv_bias',
                                      'dt_bias', 'A_log'))
    conv = proj.shape[1] - heads
    inner = conv - 2 * N
    K, t = w.shape[0], proj.shape[0]
    xp = np.pad(proj[:, :conv], ((K - 1, 0), (0, 0)))
    xbc = sum(w[k] * xp[k:k + t] for k in range(K)) + b
    xbc = xbc / (1.0 + np.exp(-xbc))
    dt = np.logaddexp(0.0, proj[:, conv:] + dt_bias)
    x = xbc[:, :inner].reshape(t, heads, inner // heads)
    Br = xbc[:, inner:inner + N] @ np.asarray(r, f64).T        # [T, m]
    decay = np.exp(dt * -np.exp(A_log))                        # [T, H]
    R = np.zeros((heads, inner // heads, Br.shape[1]), f64)
    for i in range(t):
        R = decay[i][:, None, None] * R \
            + (dt[i][:, None] * x[i])[..., None] * Br[i][None, None, :]
    return R


@functools.partial(jax.jit, static_argnames=('scale',))
def _head(x, table, *, scale):
    """x [K, hidden] against the tied table, a block of the vocabulary
    at a time (the table is never upcast whole)."""
    V, h = table.shape
    n = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    blocks = table.reshape(n, V // n, h)
    y = jax.lax.map(lambda rows: x @ rows.astype(F32).T, blocks)
    return jnp.moveaxis(y, 0, 1).reshape(x.shape[0], V) / scale


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
            out.append(_head(x, _low(params['model.embed.weight'],
                                     weights_as),
                             scale=float(model['logits_scaling'])))
    return jnp.stack(out)
