"""Routed-expert decoders with a shared expert, sigmoid router scores,
gated attention and leading dense layers (the Trinity family,
`model_type: afmoe`; the block published as
`transformers/models/afmoe/modeling_afmoe.py`): two kinds of layer in
one model by attention (window layers that rotate, full layers that
carry no positions) and two by what follows it (a dense SiLU MLP in the
leading `num_dense_layers`, routed experts beside a shared expert
after).

Per layer `l`, input `x [T, hidden]`:

    h   = RMSNorm_in(x)
    q   = RMSNorm_q(heads(h W_q))  k = RMSNorm_k(heads(h W_k))  v = heads(h W_v)
    if window layer:  q, k = rotary(q), rotary(k)        # full: NO positions
    a   = causal attention (a window layer: the last `window` keys)
    a   = a * sigmoid(h W_g)                             # before W_o
    x1  = x + RMSNorm_post_attn(a W_o)
    h2  = RMSNorm_pre_mlp(x1)
    dense layer:   y = W_down (silu(W_gate h2) * (W_up h2))
    routed layer:  s   = sigmoid(h2 W_r)                 # float32
                   top = the k largest of (s + b)        # b moves the CHOICE only
                   w   = route_scale * s[top] / (sum s[top] + 1e-20)
                   y   = shared(h2) + sum_{e in top} w_e expert_e(h2)
    x2  = x1 + RMSNorm_post_mlp(y)

and `x0 = embed[ids] * sqrt(hidden)` before layer 0 (`mup_enabled`).

What is shared with `routed_window.py`, as that module's functions and
Layers: the expert product (`chosen_experts`: a prefill's grouped
program, a decode step's dense one, the counts), the router's float32
product (`router_logits`: an eighth choice of 128 stands as close to
the ninth as a sixth of 64 to the seventh), the three ways of
attention (`attend`), `RoutedExperts`, the serving entry points
(`prefill`, `decode_step`, `forward` and the `_run` they share: this
class is that one with another `_embed` and `_block`).  From `decoder_parts.py`: `project_heads` (q/k norms,
rotary where positions are given), `gated_mlp` for the dense layers and
the shared expert, the precision rule.  Dropless; a pad row changes no
other row.

The cache is the two-group `LayerGroupKVCache`.  The leading dense
layer has no router: it hands the decode module neither counts nor
taps, so `cache_spec()` names the layers that are tapped (the first
ROUTED full and window layers) and `STEP_STATS` count the routed
layers only.
"""
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as init
from .decoder_parts import (F32, GatedMLP, GroupedProjections, Dense,
                            RMSNorm, gated_mlp, matmul, project_heads,
                            rms_norm, sub)
from .routed_window import (RoutedExperts, RoutedWindowForCausalLM, _Table,
                            attend, chosen_experts, grouped_path,
                            router_logits)

__all__ = ['AfmoeConfig', 'AfmoeForCausalLM', 'afmoe_tiny',
           'sigmoid_top_k']


class AfmoeConfig:
    """`intermediate_size` is a routed expert's width (the name the
    routed layer's Layers and the benchmark's counts read);
    `dense_intermediate_size` the leading dense layers' MLP's; the
    shared experts are one MLP of `num_shared_experts` expert
    widths."""

    def __init__(self, vocab_size=200192, hidden_size=2048, num_layers=32,
                 num_dense_layers=2, num_heads=32, num_kv_heads=4,
                 head_dim=128, intermediate_size=1024,
                 dense_intermediate_size=6144, num_experts=128,
                 experts_per_token=8, num_shared_experts=1,
                 route_scale=2.826, window=2048,
                 window_layout=(1, 1, 1, 0) * 8, max_seq_len=131072,
                 rope_theta=1e4, rms_norm_eps=1e-5,
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
        self.num_dense_layers = num_dense_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.dense_intermediate_size = dense_intermediate_size
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.num_shared_experts = num_shared_experts
        self.route_scale = float(route_scale)
        self.window = int(window)
        self.window_layout = tuple(int(x) for x in window_layout)
        if len(self.window_layout) != num_layers:
            raise ValueError(f'the layout names {len(self.window_layout)} '
                             f'layers of {num_layers}')
        self.max_seq_len = max_seq_len
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.initializer_range = initializer_range
        self.dtype = dtype


def sigmoid_top_k(logits, bias, k, scale):
    """Sigmoid scores of every logit in float32; the k largest of
    score + `bias` are chosen (the bias moves the choice only); the
    chosen scores, renormalised to sum 1 and scaled:
    `(top_i [T, k], w [T, k])`."""
    s = jax.nn.sigmoid(logits.astype(F32))
    _, top_i = jax.lax.top_k(s + bias.astype(F32), k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, scale * top_s / (top_s.sum(-1, keepdims=True) + 1e-20)


def gate_and_project(p, attended, h):
    """The attention block's tail: `a * sigmoid(h W_g)`, elementwise on
    the heads' output BEFORE the output projection, and its
    projection: `(gated a [B, T, Hq d], (gated a) W_o)`."""
    with jax.named_scope('dec.attn_gate'):
        gated = attended * jax.nn.sigmoid(matmul(h, p['gate_proj.weight']))
    return gated, matmul(gated, p['o_proj.weight'])


# -- the Layers that own the parameters -------------------------------------------
class GatedProjections(GroupedProjections):
    """`GroupedProjections` and the projection of the attention
    output's gate, as wide as the query heads."""

    def __init__(self, cfg):
        super().__init__(cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                         cfg.head_dim, eps=cfg.rms_norm_eps,
                         std=cfg.initializer_range, dtype=cfg.dtype)
        self.gate_proj = Dense(cfg.hidden_size,
                               cfg.num_heads * cfg.head_dim,
                               std=cfg.initializer_range, dtype=cfg.dtype)


class Router(nn.Layer):
    """`weight [hidden, experts]` and the per-expert `bias` that enters
    the choice, float32 (a balancing update's accumulator; zero until
    one has run)."""

    def __init__(self, cfg):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.num_experts), dtype=cfg.dtype,
            default_initializer=init.Normal(0.0, cfg.initializer_range))
        self.bias = self.create_parameter(
            (cfg.num_experts,), dtype='float32',
            default_initializer=init.Constant(0.0))


class AfmoeLayer(nn.Layer):
    """Four norms, gated attention, and a dense MLP (`routed` false) or
    a router, the routed experts and the shared expert."""

    def __init__(self, cfg, routed):
        super().__init__()
        kw = dict(eps=cfg.rms_norm_eps, dtype=cfg.dtype)
        mlp = dict(std=cfg.initializer_range, dtype=cfg.dtype)
        self.input_norm = RMSNorm(cfg.hidden_size, **kw)
        self.attn = GatedProjections(cfg)
        self.post_attn_norm = RMSNorm(cfg.hidden_size, **kw)
        self.pre_mlp_norm = RMSNorm(cfg.hidden_size, **kw)
        if routed:
            self.router = Router(cfg)
            self.experts = RoutedExperts(cfg)
            self.shared = GatedMLP(
                cfg.hidden_size,
                cfg.num_shared_experts * cfg.intermediate_size, **mlp)
        else:
            self.mlp = GatedMLP(cfg.hidden_size,
                                cfg.dense_intermediate_size, **mlp)
        self.post_mlp_norm = RMSNorm(cfg.hidden_size, **kw)


class AfmoeDecoder(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.embed = _Table(cfg)
        self.layers = nn.LayerList([
            AfmoeLayer(cfg, routed=i >= cfg.num_dense_layers)
            for i in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps,
                            dtype=cfg.dtype)


class AfmoeForCausalLM(RoutedWindowForCausalLM):
    """`RoutedWindowForCausalLM`'s entry points (`forward`, `prefill`,
    `decode_step`, what `ServingEngine` traces) and its `_run` over
    this model's `_embed` and `_block`."""

    def __init__(self, config):
        nn.Layer.__init__(self)
        self.config = config
        self.model = AfmoeDecoder(config)
        self.lm_head = _Table(config)

    def _first_routed(self, windowed):
        cfg = self.config
        return next(i for i in range(cfg.num_dense_layers, cfg.num_layers)
                    if bool(cfg.window_layout[i]) == windowed)

    def prefill_path(self, rows, bucket):
        cfg = self.config
        experts = self.model.layers[cfg.num_dense_layers].experts
        return grouped_path(rows * bucket * cfg.experts_per_token,
                            experts.gate_proj.value,
                            experts.down_proj.value)

    def cache_spec(self):
        """`RoutedWindowForCausalLM.cache_spec` and the layers a decode
        module taps: the first routed full and window layers (the
        first window layer of all is dense here, with no router to
        tap)."""
        return dict(super().cache_spec(), tap_layers=(
            self._first_routed(False), self._first_routed(True)))

    def _embed(self, params, ids):
        return super()._embed(params, ids) * math.sqrt(
            self.config.hidden_size)

    def _block(self, p, i, x, positions, view, decoding, true_rows):
        cfg = self.config
        B, T, _ = x.shape
        eps = cfg.rms_norm_eps
        window = cfg.window if cfg.window_layout[i] else None
        with jax.named_scope('dec.norm'):
            h = rms_norm(x, p['input_norm.weight'], eps)
        with jax.named_scope('dec.attn'):
            a = sub(p, 'attn.')
            # a full layer carries no positions
            q, k, v = project_heads(
                a, h, positions if window else None,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, eps=eps, theta=cfg.rope_theta)
            y, view = attend(q, k, v, view, window, cfg.dtype)
            attended, y = gate_and_project(a, y.reshape(B, T, -1), h)
        with jax.named_scope('dec.norm'):
            x = x + rms_norm(y, p['post_attn_norm.weight'], eps)
            h = rms_norm(x, p['pre_mlp_norm.weight'], eps)
        if i < cfg.num_dense_layers:
            with jax.named_scope('dec.mlp'):
                y = gated_mlp(sub(p, 'mlp.'), h)
        else:
            rows = h.reshape(B * T, -1)
            with jax.named_scope('moe.router'):
                logits = router_logits(rows, p['router.weight'])
            with jax.named_scope('dec.moe'):
                with jax.named_scope('moe.dispatch'):
                    top_i, w = sigmoid_top_k(
                        logits, p['router.bias'], cfg.experts_per_token,
                        cfg.route_scale)
                y, stats = chosen_experts(
                    sub(p, 'experts.'), rows, top_i, w, activation='silu',
                    grouped=not decoding,
                    active=view.active if decoding else true_rows)
                with jax.named_scope('moe.shared'):
                    y = y + gated_mlp(sub(p, 'shared.'), rows)
                y = y.reshape(B, T, -1)
            if decoding:
                # the counts, and what this layer computed a row (T is
                # 1): see routed_window.py
                view = view.updated(
                    view.k_pool, view.v_pool, stats,
                    {'router': logits, 'attn': attended[:, 0],
                     'moe': y[:, 0]})
        with jax.named_scope('dec.norm'):
            x = x + rms_norm(y, p['post_mlp_norm.weight'], eps)
        return x, view


def afmoe_tiny(**kw):
    """One dense and four routed layers at the tests' widths: window |
    window, full, window, window."""
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=5,
               num_dense_layers=1, num_heads=6, num_kv_heads=2,
               head_dim=16, intermediate_size=32,
               dense_intermediate_size=96, num_experts=8,
               experts_per_token=3, window=8,
               window_layout=(1, 1, 0, 1, 1), max_seq_len=128,
               dtype='float32')
    cfg.update(kw)
    return AfmoeForCausalLM(AfmoeConfig(**cfg))
