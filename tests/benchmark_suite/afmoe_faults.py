"""Faults planted in the program's routed decoder with a shared expert
(`paddle_tpu/models/afmoe.py` and what it shares with
`routed_window.py`), for the tests on the CPU
(tests/test_afmoe_serving.py, test_benchmark_serve_routed_shared.py)
and, at the cell's own size, on the chip
(chip_control_routed_shared.py).  `plant(fault)` patches the program
and returns the call that undoes it; the reference is never touched."""
FAULTS = ('softmax_for_sigmoid', 'bias_in_the_weights',
          'weights_not_renormalised', 'no_route_scale',
          'shared_expert_left_out', 'relu_for_silu', 'no_attention_gate',
          'gate_after_the_output_projection', 'rotary_on_a_full_layer',
          'no_qk_norm', 'post_norm_left_out',
          'router_reads_the_layers_input', 'dense_layer_routed',
          'embedding_unscaled', 'window_off_by_one_block')


def _patch(undo, obj, name, value):
    undo.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


def plant(fault):
    import types
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import afmoe as af
    from paddle_tpu.models import routed_window as rw
    from paddle_tpu.serving.kv_cache import (GroupedCacheView,
                                             LayerGroupKVCache)
    F32 = jnp.float32
    undo = []

    def scoring(score, weigh):
        """`sigmoid_top_k` with the scores by `score(logits)` and the
        chosen ones' weights by `weigh(chosen scores, chosen biases,
        scale)`."""
        def planted(logits, bias, k, scale):
            s = score(logits.astype(F32))
            b = bias.astype(F32)
            _, top_i = jax.lax.top_k(s + b, k)
            return top_i, weigh(jnp.take_along_axis(s, top_i, axis=-1),
                                b[top_i], scale)
        _patch(undo, af, 'sigmoid_top_k', planted)

    def renormalised(s, b, scale):
        return scale * s / (s.sum(-1, keepdims=True) + 1e-20)

    if fault == 'softmax_for_sigmoid':
        scoring(lambda x: jax.nn.softmax(x, axis=-1), renormalised)
    elif fault == 'bias_in_the_weights':
        scoring(jax.nn.sigmoid,
                lambda s, b, scale: renormalised(s + b, b, scale))
    elif fault == 'weights_not_renormalised':
        scoring(jax.nn.sigmoid, lambda s, b, scale: scale * s)
    elif fault == 'no_route_scale':
        scoring(jax.nn.sigmoid,
                lambda s, b, scale: renormalised(s, b, 1.0))
    elif fault == 'shared_expert_left_out':
        sub = af.sub
        _patch(undo, af, 'sub', lambda params, prefix: {
            k: jnp.zeros_like(v) for k, v in sub(params, prefix).items()
        } if prefix == 'shared.' else sub(params, prefix))
    elif fault == 'relu_for_silu':
        # the routed experts' gate, in the grouped and the dense program
        # (on the chip a prefill's Pallas epilogue too)
        _patch(undo, rw, '_gated_silu', rw._gated)
        kernel = rw.gm.grouped_gate_up
        _patch(undo, rw.gm, 'grouped_gate_up',
               lambda rows, wg, wu, sizes, dtype, activation: kernel(
                   rows, wg, wu, sizes, dtype, 'relu'))
    elif fault == 'no_attention_gate':
        _patch(undo, af, 'gate_and_project', lambda p, attended, h: (
            attended, af.matmul(attended, p['o_proj.weight'])))
    elif fault == 'gate_after_the_output_projection':
        # the gate's first `hidden` columns on the projected output
        def late(p, attended, h):
            y = af.matmul(attended, p['o_proj.weight'])
            gate = jax.nn.sigmoid(af.matmul(h, p['gate_proj.weight']))
            return attended, y * gate[..., :y.shape[-1]]
        _patch(undo, af, 'gate_and_project', late)
    elif fault == 'rotary_on_a_full_layer':
        sound = af.project_heads

        def rotated(p, x, positions, **kw):
            if positions is None:
                B, T, _ = x.shape
                positions = jnp.broadcast_to(
                    jnp.arange(T, dtype=jnp.int32)[None], (B, T))
            return sound(p, x, positions, **kw)

        _patch(undo, af, 'project_heads', rotated)
    elif fault == 'no_qk_norm':
        def plain(p, x, positions, *, eps, **kw):
            del eps                         # the norms' alone
            return rw.plain_heads(p, x, positions, **kw)

        _patch(undo, af, 'project_heads', plain)
    elif fault == 'post_norm_left_out':
        # the layer's dictionary (cut out of the model's by the shared
        # `_run`) hands None for the MLP's post-norm, and a norm
        # without a weight is left out
        sub, norm = rw.sub, af.rms_norm

        def without(params, prefix):
            out = sub(params, prefix)
            if 'post_mlp_norm.weight' in out:
                out['post_mlp_norm.weight'] = None
            return out

        _patch(undo, rw, 'sub', without)
        _patch(undo, af, 'rms_norm', lambda x, w, eps: x if w is None
               else norm(x, w, eps))
    elif fault == 'router_reads_the_layers_input':
        # the layer's normed INPUT, as `project_heads` was handed it
        seen = {}
        heads, logits = af.project_heads, af.router_logits

        def remembering(p, x, positions, **kw):
            seen['h'] = x
            return heads(p, x, positions, **kw)

        _patch(undo, af, 'project_heads', remembering)
        _patch(undo, af, 'router_logits', lambda rows, w: logits(
            seen['h'].reshape(rows.shape), w))
    elif fault == 'dense_layer_routed':
        # layer 0 routes too, through the next layer's router, experts
        # and shared expert (it has none of its own)
        import copy
        sound = af.AfmoeForCausalLM._run

        def routed_everywhere(self, params, *args):
            dense = self.config.num_dense_layers
            borrowed = dict(params)
            for name, w in params.items():
                if name.startswith(f'model.layers.{dense}.') and any(
                        part in name for part in ('.router.', '.experts.',
                                                  '.shared.')):
                    for i in range(dense):
                        borrowed[name.replace(
                            f'layers.{dense}.', f'layers.{i}.')] = w
            cfg = self.config
            self.config = copy.copy(cfg)
            self.config.num_dense_layers = 0
            try:
                return sound(self, borrowed, *args)
            finally:
                self.config = cfg

        _patch(undo, af.AfmoeForCausalLM, '_run', routed_everywhere)
    elif fault == 'embedding_unscaled':
        _patch(undo, af, 'math',
               types.SimpleNamespace(sqrt=lambda x: 1.0))
    elif fault == 'window_off_by_one_block':
        # the band starts a block early: a block of keys too many
        sound = LayerGroupKVCache.decode_views

        def early(self, arrays, where, ctx, active):
            views = sound(self, arrays, where, ctx, active)
            return [v if w is None else GroupedCacheView(
                v.k_pool, v.v_pool, v.block_table, v.slots, v.lens,
                jnp.maximum(v.first - self.block_size, 0), v.active)
                for v, w in zip(views, self.layer_windows)]

        _patch(undo, LayerGroupKVCache, 'decode_views', early)
        # ... and the allocator keeps that block, so it is a real key
        needed = LayerGroupKVCache._first_needed
        _patch(undo, LayerGroupKVCache, '_first_needed',
               lambda self, written: max(0, needed(self, written) - 1))
    else:
        raise ValueError(f'unknown fault {fault!r}')

    def restore():
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)
    return restore
