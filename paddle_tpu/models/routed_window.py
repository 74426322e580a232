"""Routed-expert decoders with window and full attention layers mixed
(the SmallThinker family, arXiv:2507.20984): today's decoder block
(RMSNorm, rotary positions, grouped-query projections, untied head;
`decoder_parts.py`) with a dropless top-k routed ReGLU layer where the
MLP would stand, a router that reads the layer's NORMED INPUT (so a
deployment can fetch the chosen experts while attention runs), and
per-layer layouts that say which layers see a window of the last
`window` positions and which carry rotary positions.

Per layer `l`, input `x [T, hidden]`:

    h   = RMSNorm(x)
    r   = h W_r                               # router, float32 (below)
    q, k, v = heads of h                      # no bias, no q/k norm
    if rope_layout[l]:  q, k = rotary(q), rotary(k)
    a   = causal attention, and where window_layout[l] only the last
          `window` keys, the query's own among them
    x1  = x + a W_o
    h2  = RMSNorm(x1)
    top = the k largest of r;  p = softmax(r[top])      # float32
    y   = sum_{e in top} p_e W_down,e (relu(W_gate,e h2) * (W_up,e h2))
    x2  = x1 + y

Routing is DROPLESS: every token's k experts compute it, whatever the
batch, so a pad row of a prefill bucket or of a batch bucket changes no
other row's result (it costs compute) and the serving engine takes the
model (`serving/engine.py` refuses `SwitchMoE`, which has a capacity).

Precision follows `decoder_parts.py`: weights in the model's dtype, a
matmul rounds its activation to the weight's dtype on the way in and
returns float32, float32 between.  The one exception is the router:
rounding h to bfloat16 moves its 64 logits by about their hundredth,
enough to flip a sixth choice that stands close to the seventh, and a
flipped expert is another function; so the router's product takes h as
it is, float32, against the router's weights cast up, at the highest
matmul precision (2560 x 64: no traffic beside the experts).

Two programs for the expert product, one mathematics.  A prefill's
rows (`grouped=True`) are sorted by expert and each expert's rows
multiply its matrices once: on the chip through the Pallas grouped
matmul of `ops/grouped_matmul.py` (row tiles of 128 visited by
what the router sent, gate and up in one pass over a row tile with
`_gated` as its epilogue, the down projection a second call; some 1,150
rows an expert at 12k tokens, compute-bound), and through
`jax.lax.ragged_dot` wherever that kernel's gate refuses (the CPU, a
mesh, other dtypes and widths).  The bucket's pad positions (`active`
false) sort behind the last expert, are multiplied by nothing and come
back as zeros: prompts are padded on the right and attention is causal,
so no true position ever read one.  A decode step's few rows (32 rows
hit 95% of 64 experts) take a dense product over all experts, weighted
by a [rows, experts] matrix that is zero off the chosen ones: bound by
the same bytes, the experts' weights, and no sort in the token step.

What another routed decoder shares (`models/afmoe.py` does): the
expert product takes the CHOICE from its caller, `chosen_experts(p,
h2, top_i, w, activation=...)`, so scoring (softmax of the largest
logits here; sigmoid scores, a bias and a scale there) and the gate's
activation are the model's own; `routed_experts` is this model's
composition of the two.  The three ways of attention (`attend`,
`attend_whole`) are functions of this module for the same reason.

The cache: `serving_state = 'paged'` with `cache_spec()`, from which
the engine builds a `LayerGroupKVCache` (`serving/kv_cache.py`): full
and window layers hold their own pools and tables, keys stored as
attention reads them (rotated in the layers that rotate).  `prefill`
returns the logits at each row's last true position only.
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as init
from ..ops import grouped_matmul as gm
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import paged_attention, write_kv
from .decoder_parts import Dense, RMSNorm, matmul, rms_norm, rotary, sub

__all__ = ['RoutedWindowConfig', 'RoutedWindowForCausalLM',
           'routed_window_tiny', 'routed_experts', 'chosen_experts',
           'top_k_softmax', 'router_logits', 'attend', 'attend_whole']

F32 = jnp.float32
STEP_STATS = ('moe_assignments', 'moe_experts_hit', 'moe_max_load')


class RoutedWindowConfig:
    def __init__(self, vocab_size=151936, hidden_size=2560, num_layers=52,
                 num_heads=28, num_kv_heads=4, head_dim=128,
                 intermediate_size=768, num_experts=64, experts_per_token=6,
                 window=4096, window_layout=(0, 1, 1, 1) * 13,
                 rope_layout=(0, 1, 1, 1) * 13, max_seq_len=16384,
                 rope_theta=1.5e6, rms_norm_eps=1e-6,
                 initializer_range=0.02, dtype='bfloat16'):
        if num_heads % num_kv_heads:
            raise ValueError(f'{num_heads} query heads do not group '
                             f'onto {num_kv_heads} key/value heads')
        if experts_per_token > num_experts:
            raise ValueError(f'{experts_per_token} experts a token of '
                             f'{num_experts}')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.window = int(window)
        # the published layouts name every layer of the whole model; a
        # model of fewer layers (a pipeline stage) takes their head
        self.window_layout = tuple(int(x) for x in window_layout)[:num_layers]
        self.rope_layout = tuple(int(x) for x in rope_layout)[:num_layers]
        if len(self.window_layout) != num_layers \
                or len(self.rope_layout) != num_layers:
            raise ValueError(f'layouts name {len(self.window_layout)} and '
                             f'{len(self.rope_layout)} layers of '
                             f'{num_layers}')
        self.max_seq_len = max_seq_len
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.initializer_range = initializer_range
        self.dtype = dtype


# -- the routed layer, as pure functions ------------------------------------------
def router_logits(h, weight):
    """h W_r with h as it is (float32) and the weight cast up, at the
    highest matmul precision: see this file's header."""
    return jnp.matmul(h.astype(F32), weight.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def _gated(g, u, dtype):
    """relu(gate) * up, rounded for the down projection."""
    return (jax.nn.relu(g) * u).astype(dtype)


def _gated_silu(g, u, dtype):
    """silu(gate) * up, rounded for the down projection."""
    return (jax.nn.silu(g) * u).astype(dtype)


def _epilogue(activation):
    """The gated pair's epilogue by the name the Pallas kernel takes
    (`gm.ACTIVATIONS`), found through the module at each call."""
    return {'relu': _gated, 'silu': _gated_silu}[activation]


def top_k_softmax(logits, k):
    """The k largest logits a row, and softmax over those k in
    float32: `(top_i [T, k], w [T, k])`."""
    top_v, top_i = jax.lax.top_k(logits, k)
    return top_i, jax.nn.softmax(top_v.astype(F32), axis=-1)


def routed_experts(p, h2, logits, k, *, grouped, active=None):
    """This model's routed layer: the k largest of `logits [T,
    experts]` (the router's, of the layer's normed INPUT), softmax
    over them, ReLU-gated experts (`chosen_experts`)."""
    with jax.named_scope('moe.dispatch'):
        top_i, w = top_k_softmax(logits, k)
    return chosen_experts(p, h2, top_i, w, activation='relu',
                          grouped=grouped, active=active)


def chosen_experts(p, h2, top_i, w, *, activation, grouped, active=None):
    """sum_j w[:, j] expert top_i[:, j] of rows `h2 [T, hidden]`,
    [T, hidden] float32, and the layer's counts: assignments made,
    distinct experts hit and the largest expert's load, over the rows
    `active` marks (all of them where it is None).  A grouped product
    computes the active rows only; the others' output is zero.

    `p` holds `gate_proj`, `up_proj` [experts, hidden, width] and
    `down_proj` [experts, width, hidden]; an expert is
    down(act(gate x) * (up x)) with `activation` naming act ('relu',
    'silu').  `grouped` picks the program (this file's header), never
    the mathematics."""
    T, k = top_i.shape
    wg, wu, wd = p['gate_proj'], p['up_proj'], p['down_proj']
    E = wg.shape[0]
    gated = _epilogue(activation)
    with jax.named_scope('moe.dispatch'):
        hit = jnp.zeros((T, E), jnp.int32).at[
            jnp.arange(T)[:, None], top_i].set(1)
        if active is not None:
            hit = hit * active.astype(jnp.int32)[:, None]
        load = hit.sum(0)
        stats = jnp.stack([load.sum(), (load > 0).sum(), load.max()])
    x = h2.astype(wg.dtype)
    if grouped:
        return _grouped(x, top_i, w, wg, wu, wd, active, activation), stats
    with jax.named_scope('moe.dispatch'):
        mix = jnp.zeros((T, E), F32).at[
            jnp.arange(T)[:, None], top_i].set(w)
    with jax.named_scope('moe.experts'):
        # [1, T, h] @ [E, h, f] (as an einsum XLA's CPU backend merges
        # the two products into a bfloat16 dot its runtime lacks)
        g = jnp.matmul(x[None], wg, preferred_element_type=F32)
        u = jnp.matmul(x[None], wu, preferred_element_type=F32)
        y = jnp.einsum('etf,efh->eth', gated(g, u, wd.dtype), wd,
                       preferred_element_type=F32)
    with jax.named_scope('moe.dispatch'):
        out = jnp.einsum('te,eth->th', mix, y,
                         precision=jax.lax.Precision.HIGHEST)
    return out, stats


def grouped_path(rows, wg, wd):
    """'kernel' where the grouped product of `rows` sorted rows takes
    `ops/grouped_matmul.py`, 'ragged_dot' where its gate refuses."""
    return 'kernel' if gm.can_use_pallas(rows, wg, 2) \
        and gm.can_use_pallas(rows, wd) else 'ragged_dot'


def _grouped(x, top_i, w, wg, wu, wd, active, activation):
    """The expert product of rows x [T, hidden] (in the weights'
    dtype) routed to `top_i` [T, k] with weights `w` [T, k]: rows
    sorted by expert, each expert's rows against its matrices once;
    rows that are not `active` behind the last expert, in no group."""
    (T, k), E = top_i.shape, wg.shape[0]
    with jax.named_scope('moe.dispatch'):
        if active is not None:
            top_i = jnp.where(active[:, None], top_i, E)
        flat = top_i.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        rows = x[order // k]                         # [T k, hidden]
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    with jax.named_scope('moe.experts'):
        if grouped_path(T * k, wg, wd) == 'kernel':
            y = gm.grouped_matmul(
                gm.grouped_gate_up(rows, wg, wu, sizes, wd.dtype,
                                   activation), wd, sizes)
        else:
            g = jax.lax.ragged_dot(rows, wg, sizes,
                                   preferred_element_type=F32)
            u = jax.lax.ragged_dot(rows, wu, sizes,
                                   preferred_element_type=F32)
            y = jax.lax.ragged_dot(_epilogue(activation)(g, u, wd.dtype),
                                   wd, sizes, preferred_element_type=F32)
    with jax.named_scope('moe.dispatch'):
        # back to the tokens' own order, then each token's k in the
        # order its router chose them: a row's sum does not depend on
        # the rows around it
        y = y[jnp.argsort(order)].reshape(T, k, -1)
        out = (y * w[:, :, None]).sum(1)
        if active is not None:
            # whatever a product left behind its last group
            out = jnp.where(active[:, None], out, 0.0)
        return out


def plain_heads(p, x, positions, *, num_heads, num_kv_heads, head_dim,
                theta):
    """x [B, T, h] -> q [B,T,Hq,d], k and v [B,T,Hkv,d], float32; no
    bias and no q/k norm; rotated where `positions` is given."""
    B, T, _ = x.shape
    q = matmul(x, p['q_proj.weight']).reshape(B, T, num_heads, head_dim)
    k = matmul(x, p['k_proj.weight']).reshape(B, T, num_kv_heads,
                                              head_dim)
    v = matmul(x, p['v_proj.weight']).reshape(B, T, num_kv_heads,
                                              head_dim)
    if positions is not None:
        q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    return q, k, v


# -- attention, three ways ----------------------------------------------------------
def attend_whole(q, k, v, window, dtype):
    """Whole sequences [B, T, heads, d] through the flash kernel (its
    reference off the chip): causal, banded in a window layer.  q's
    rows go batch, key/value head, head of the group, which is the
    order the kernel's index map groups them by.  The operands are
    rounded to `dtype`, the weights', on the way in, as every matmul's
    are (`decoder_parts.matmul`): the MXU takes one bfloat16 pass over
    float32 operands anyway, and half the bytes move.  Tiles of (512,
    1024) where the length allows: the largest that PR 32's sweep
    found fastest at 2,048."""
    B, T, H, d = q.shape
    dtype = jnp.dtype(dtype)

    def rows(x):
        return jnp.swapaxes(x, 1, 2).reshape(-1, T, d).astype(dtype)

    blocks = dict(block_q=512, block_k=1024) if T % 1024 == 0 else {}
    y = flash_attention(rows(q), rows(k), rows(v), causal=True,
                        window=window, **blocks)
    return jnp.swapaxes(y.reshape(B, H, T, d), 1, 2).astype(F32)


def attend(q, k, v, view, window, dtype):
    """(attention output [B, T, heads, d] float32, the layer's view
    with what it wrote) for `view` None (`forward`), a `PrefillKV` (a
    prefill: whole sequences, the keys and values handed back) or a
    paged view (a decode step: one token a row through the pools)."""
    B, T = q.shape[:2]
    if view is None:                                  # forward()
        return attend_whole(q, k, v, window, dtype), None
    if not getattr(view, 'paged', False):             # prefill
        return attend_whole(q, k, v, window, dtype), view.updated(
            k.reshape(B, T, -1), v.reshape(B, T, -1))
    # one token a row, through the paged pools
    kp, vp = write_kv(view.k_pool, view.v_pool, k.reshape(B, -1),
                      v.reshape(B, -1), view.block_table, view.slots)
    y = paged_attention(q[:, 0], kp, vp, view.block_table, view.lens,
                        view.first)
    return y[:, None], view.updated(kp, vp)


# -- the Layers that own the parameters -------------------------------------------
class PlainProjections(nn.Layer):
    """q on `num_heads` heads, k and v on `num_kv_heads`, the output
    projection back; no bias, no norms."""

    def __init__(self, cfg):
        super().__init__()
        kw = dict(std=cfg.initializer_range, dtype=cfg.dtype)
        self.q_proj = Dense(cfg.hidden_size, cfg.num_heads * cfg.head_dim,
                            **kw)
        self.k_proj = Dense(cfg.hidden_size,
                            cfg.num_kv_heads * cfg.head_dim, **kw)
        self.v_proj = Dense(cfg.hidden_size,
                            cfg.num_kv_heads * cfg.head_dim, **kw)
        self.o_proj = Dense(cfg.num_heads * cfg.head_dim, cfg.hidden_size,
                            **kw)


class RoutedExperts(nn.Layer):
    """`num_experts` ReGLU experts, stacked: gate and up
    [experts, hidden, width], down [experts, width, hidden]."""

    def __init__(self, cfg):
        super().__init__()
        E, h, f = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
        normal = init.Normal(0.0, cfg.initializer_range)
        self.gate_proj = self.create_parameter(
            (E, h, f), dtype=cfg.dtype, default_initializer=normal)
        self.up_proj = self.create_parameter(
            (E, h, f), dtype=cfg.dtype, default_initializer=normal)
        self.down_proj = self.create_parameter(
            (E, f, h), dtype=cfg.dtype, default_initializer=normal)


class RoutedWindowLayer(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        kw = dict(eps=cfg.rms_norm_eps, dtype=cfg.dtype)
        self.input_norm = RMSNorm(cfg.hidden_size, **kw)
        self.router = Dense(cfg.hidden_size, cfg.num_experts,
                            std=cfg.initializer_range, dtype=cfg.dtype)
        self.attn = PlainProjections(cfg)
        self.post_norm = RMSNorm(cfg.hidden_size, **kw)
        self.experts = RoutedExperts(cfg)


class _Table(nn.Layer):
    """[vocab, hidden] rows: the embedding, and the untied head."""

    def __init__(self, cfg):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=init.Normal(0.0, cfg.initializer_range))


class RoutedWindowDecoder(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.embed = _Table(cfg)
        self.layers = nn.LayerList([RoutedWindowLayer(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps,
                            dtype=cfg.dtype)


class RoutedWindowForCausalLM(nn.Layer):
    """`forward(ids)` is the whole model's logits; `prefill` and
    `decode_step` are what `ServingEngine` traces."""

    serving_state = 'paged'
    # what a decode step's layers hand back through their cache views,
    # summed by the engine on the host (`ServingEngine.counts()`)
    step_stat_names = STEP_STATS

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = RoutedWindowDecoder(config)
        self.lm_head = _Table(config)

    def prefill_path(self, rows, bucket):
        """Which program the routed layers of a prefill of `rows`
        prompts padded to `bucket` take (`grouped_path`): what the
        engine counts as `moe_kernel_prefills`."""
        experts = self.model.layers[0].experts
        return grouped_path(
            rows * bucket * self.config.experts_per_token,
            experts.gate_proj.value, experts.down_proj.value)

    def cache_spec(self):
        """What the engine's `LayerGroupKVCache` is built from: each
        layer's window (None: a full layer) and the key/value heads."""
        cfg = self.config
        return {'layer_windows': tuple(
                    cfg.window if w else None for w in cfg.window_layout),
                'num_kv_heads': cfg.num_kv_heads,
                'head_dim': cfg.head_dim}

    # -- the one forward ------------------------------------------------------
    def _embed(self, params, ids):
        """Token ids -> the residual stream before layer 0, float32."""
        return params['model.embed.weight'][ids].astype(F32)

    def _block(self, p, i, x, positions, view, decoding, true_rows):
        """Layer `i` with parameters `p` over x [B, T, hidden]:
        (x, the layer's view with what it wrote).  `true_rows` [B T]
        marks a prefill's true positions (None: all)."""
        cfg = self.config
        B, T, _ = x.shape
        with jax.named_scope('dec.norm'):
            h = rms_norm(x, p['input_norm.weight'], cfg.rms_norm_eps)
        with jax.named_scope('moe.router'):
            logits = router_logits(h.reshape(B * T, -1),
                                   p['router.weight'])
        with jax.named_scope('dec.attn'):
            a = sub(p, 'attn.')
            q, k, v = plain_heads(
                a, h, positions if cfg.rope_layout[i] else None,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, theta=cfg.rope_theta)
            y, view = attend(
                q, k, v, view,
                cfg.window if cfg.window_layout[i] else None, cfg.dtype)
            attended = y.reshape(B, T, -1)
            x = x + matmul(attended, a['o_proj.weight'])
        with jax.named_scope('dec.norm'):
            h = rms_norm(x, p['post_norm.weight'], cfg.rms_norm_eps)
        with jax.named_scope('dec.moe'):
            y, stats = routed_experts(
                sub(p, 'experts.'), h.reshape(B * T, -1), logits,
                cfg.experts_per_token, grouped=not decoding,
                active=view.active if decoding else true_rows)
            x = x + y.reshape(B, T, -1)
        if decoding:
            # the counts, and what this layer computed a row (T is
            # 1): the cache hands its tapped layers' out of the
            # decode module, the rest is never materialised
            view = view.updated(
                view.k_pool, view.v_pool, stats,
                {'router': logits, 'attn': attended[:, 0], 'moe': y})
        return x, view

    def _run(self, params, ids, positions, views, lengths, last_only):
        """Embedding, every layer's `_block`, the final norm and the
        head; a decoder of another block overrides `_embed` and
        `_block`."""
        cfg = self.config
        B, T = ids.shape
        decoding = views is not None and getattr(views[0], 'paged', False)
        # a prefill's true positions: the bucket's pad rows on the right
        # are routed nowhere
        true_rows = None if lengths is None else (
            jnp.arange(T, dtype=jnp.int32)[None, :]
            < lengths[:, None].astype(jnp.int32)).reshape(B * T)
        with jax.named_scope('dec.embed'):
            x = self._embed(params, ids)
        new_views = []
        for i in range(cfg.num_layers):
            x, view = self._block(
                sub(params, f'model.layers.{i}.'), i, x, positions,
                None if views is None else views[i], decoding, true_rows)
            new_views.append(view)
        if last_only:
            x = jnp.take_along_axis(
                x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
        with jax.named_scope('dec.norm'):
            x = rms_norm(x, params['model.norm.weight'], cfg.rms_norm_eps)
        with jax.named_scope('dec.head'):
            head = params['lm_head.weight']
            logits = jnp.einsum('bth,vh->btv', x.astype(head.dtype), head,
                                preferred_element_type=F32)
        return logits, new_views

    # -- what the serving engine calls ----------------------------------------
    def prefill(self, params, buffers, ids, pos, caches):
        """Padded prompts [B, P] from position `pos`.  `caches` is one
        `PrefillKV` a layer carrying the rows' true `lengths`; returns
        the logits [B, 1, V] at each row's last true position and the
        views with each layer's keys and values [B, P, kv heads * d]."""
        del buffers
        B, T = ids.shape
        positions = jnp.asarray(pos, jnp.int32).reshape(-1, 1) \
            + jnp.arange(T, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (B, T))
        return self._run(params, ids, positions, caches,
                         caches[0].lengths, True)

    def decode_step(self, params, buffers, tok, pos, caches):
        """One token a row: `tok` [B, 1], `pos` [B] its absolute
        position, `caches` one `GroupedCacheView` a layer.  Each view
        comes back with the layer's counts (`stats`) and, a row, its
        router's logits, its attention output [heads * d] and its
        routed layer's output [hidden] (`taps`)."""
        del buffers
        positions = jnp.asarray(pos, jnp.int32).reshape(-1, 1)
        return self._run(params, tok, positions, caches, None, False)

    def forward(self, input_ids):
        """Logits [B, T, V] float32 of whole sequences."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B, T = ids.shape
        params, _ = self.functional_state()
        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        logits, _ = self._run(params, ids, positions, None, None, False)
        return Tensor._from_value(logits)


def routed_window_tiny(**kw):
    """One period of four layers at the tests' widths."""
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=4, num_heads=6,
               num_kv_heads=2, head_dim=16, intermediate_size=32,
               num_experts=8, experts_per_token=3, window=8,
               window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
               max_seq_len=128, dtype='float32')
    cfg.update(kw)
    return RoutedWindowForCausalLM(RoutedWindowConfig(**cfg))
