"""Retention decoders: today's decoder block (RMSNorm, rotary positions,
grouped-query projections with q/k norms, gated SiLU MLP, untied head;
`decoder_parts.py`) with power retention of degree 2 where attention
would stand (`ops/power_retention.py`, arXiv:2507.04239).

Per layer, with `h` the normed input and one log decay a key/value
head, `g = log sigmoid(W_g h)` in float32 (no bias: the state is a
function of k, v and the gate, so only a gate shared by a group lets
grouped query heads share a state):

    q, k, v = heads of h (q/k normed over the head dimension, rotated)
    y = power_retention(q, k, v, g)         # no output gate or norm
    x = x + W_o y;  x = x + MLP(RMSNorm(x))

A sequence's memory is one state of fixed size a layer, whatever its
length, so the model asks the serving engine for a recurrent-state
cache (`serving_state`) instead of the paged KV pool, and `prefill`
returns the logits at each row's last true position only (a 150k-row
head over every prompt position would cost half the prefill).
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as init
from ..ops import power_retention as pr
from .decoder_parts import (Dense, GatedMLP, GroupedProjections, RMSNorm,
                            gated_mlp, matmul, project_heads, rms_norm,
                            sub)

__all__ = ['RetentionConfig', 'RetentionForCausalLM', 'retention_tiny']

F32 = jnp.float32


class RetentionConfig:
    def __init__(self, vocab_size=151936, hidden_size=5120, num_layers=40,
                 num_heads=40, num_kv_heads=8, head_dim=128,
                 intermediate_size=17408, max_seq_len=32768,
                 rope_theta=1e6, rms_norm_eps=1e-6, retention_degree=2,
                 tie_word_embeddings=False, initializer_range=0.02,
                 dtype='bfloat16'):
        if retention_degree != 2:
            raise ValueError('power retention is implemented for '
                             f'degree 2, not {retention_degree}')
        if tie_word_embeddings:
            raise ValueError('the retention decoders have an untied head')
        if num_heads % num_kv_heads:
            raise ValueError(f'{num_heads} query heads do not group '
                             f'onto {num_kv_heads} key/value heads')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.max_seq_len = max_seq_len
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.retention_degree = retention_degree
        self.tie_word_embeddings = tie_word_embeddings
        self.initializer_range = initializer_range
        self.dtype = dtype


class RetentionLayer(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        kw = dict(std=cfg.initializer_range, dtype=cfg.dtype)
        self.input_norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps,
                                  dtype=cfg.dtype)
        self.attn = GroupedProjections(
            cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, eps=cfg.rms_norm_eps, **kw)
        self.attn.g_proj = Dense(cfg.hidden_size, cfg.num_kv_heads, **kw)
        self.post_norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps,
                                 dtype=cfg.dtype)
        self.mlp = GatedMLP(cfg.hidden_size, cfg.intermediate_size, **kw)


class _Table(nn.Layer):
    """[vocab, hidden] rows: the embedding, and the untied head."""

    def __init__(self, cfg):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=init.Normal(0.0, cfg.initializer_range))


class RetentionDecoder(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.embed = _Table(cfg)
        self.layers = nn.LayerList([RetentionLayer(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps,
                            dtype=cfg.dtype)


class RetentionForCausalLM(nn.Layer):
    """`forward(ids)` is the whole model's logits; `prefill` and
    `decode_step` are what `ServingEngine` traces."""

    # the per-sequence memory this model needs of a serving engine
    serving_state = 'recurrent'

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = RetentionDecoder(config)
        self.lm_head = _Table(config)

    def state_spec(self):
        """Shapes of one sequence's state, for the engine's cache."""
        cfg = self.config
        return {'num_layers': cfg.num_layers,
                'num_kv_heads': cfg.num_kv_heads,
                'value_dim': cfg.head_dim,
                'features': pr.num_features(cfg.head_dim)}

    # -- the one forward ------------------------------------------------------
    def _retain(self, q, k, v, g, view, lengths):
        if view is not None and view.S is not None:     # one token a row
            y, S, z = pr.retention_decode(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], view.S, view.z,
                view.slots, view.active)
            return y[:, None], view.updated(S, z)
        y, (S, z) = pr.retention_prefill(q, k, v, g, lengths)
        return y, None if view is None else view.updated(S, z)

    def _run(self, params, ids, positions, views, lengths, last_only):
        cfg = self.config
        B, T = ids.shape
        heads = dict(num_heads=cfg.num_heads,
                     num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                     eps=cfg.rms_norm_eps, theta=cfg.rope_theta)
        with jax.named_scope('dec.embed'):
            x = params['model.embed.weight'][ids].astype(F32)
        new_views = []
        for i in range(cfg.num_layers):
            p = sub(params, f'model.layers.{i}.')
            with jax.named_scope('dec.norm'):
                h = rms_norm(x, p['input_norm.weight'], cfg.rms_norm_eps)
            with jax.named_scope('dec.attn'):
                a = sub(p, 'attn.')
                q, k, v = project_heads(a, h, positions, **heads)
                g = jax.nn.log_sigmoid(matmul(h, a['g_proj.weight']))
                y, view = self._retain(
                    q, k, v, g, None if views is None else views[i],
                    lengths)
                new_views.append(view)
                x = x + matmul(y.reshape(B, T, -1), a['o_proj.weight'])
            with jax.named_scope('dec.norm'):
                h = rms_norm(x, p['post_norm.weight'], cfg.rms_norm_eps)
            with jax.named_scope('dec.mlp'):
                x = x + gated_mlp(sub(p, 'mlp.'), h)
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
        """Padded prompts [B, P] from the empty state.  `caches` is one
        view a layer carrying the rows' true `lengths`; returns the
        logits [B, 1, V] at each row's last true position and the views
        with the states (S [B,Hkv,d,D], z [B,Hkv,D]) at those lengths:
        pad positions reach neither."""
        del buffers
        B, T = ids.shape
        lengths = caches[0].lengths
        positions = jnp.asarray(pos, jnp.int32).reshape(-1, 1) \
            + jnp.arange(T, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (B, T))
        return self._run(params, ids, positions, caches, lengths, True)

    def decode_step(self, params, buffers, tok, pos, caches):
        """One token a row: `tok` [B, 1], `pos` [B] its absolute
        position, `caches` one view a layer with the whole state
        arrays, the rows' slots and which rows are active."""
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
        logits, _ = self._run(params, ids, positions, None,
                              jnp.full((B,), T, jnp.int32), False)
        return Tensor._from_value(logits)


def retention_tiny(**kw):
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
               num_kv_heads=2, head_dim=16, intermediate_size=128,
               max_seq_len=128, dtype='float32')
    cfg.update(kw)
    return RetentionForCausalLM(RetentionConfig(**cfg))
