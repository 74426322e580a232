"""Faults planted in the program's latent-attention decoder
(`paddle_tpu/models/joyai.py` and what it shares with
`routed_window.py`), for the tests on the CPU (tests/test_joyai_serving.py,
test_benchmark_serve_mla.py) and, at the cell's own size, on the chip
(chip_control_mla.py).  `plant(fault)` patches the program and returns
the call that undoes it; the reference is never touched."""
FAULTS = ('rotate_half_for_interleaved', 'scale_of_the_whole_row',
          'kv_norm_skipped', 'w_uk_transposed', 'bias_in_the_weights',
          'no_route_scale', 'experts_outside_the_share_computed',
          'shared_expert_left_out')


def _patch(undo, obj, name, value):
    undo.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


def plant(fault):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import decoder_parts as dp
    from paddle_tpu.models import joyai as ja
    F32 = jnp.float32
    undo = []

    def renormalised(s, scale):
        return scale * s / (s.sum(-1, keepdims=True) + 1e-20)

    if fault == 'rotate_half_for_interleaved':
        _patch(undo, ja, 'rotary', lambda x, positions, theta, interleaved:
               dp.rotary(x, positions, theta))
    elif fault == 'scale_of_the_whole_row':
        # 1 / sqrt(576), the latent and the rotary key, for 1 / sqrt(192)
        _patch(undo, ja.JoyAIConfig, 'softmax_scale', property(
            lambda cfg: (cfg.kv_lora_rank + cfg.qk_rope_head_dim) ** -0.5))
    elif fault == 'kv_norm_skipped':
        def unnormed(a, h, positions, cfg):
            r = cfg.kv_lora_rank
            ckv = ja.matmul(h, a['kv_a_proj.weight'])
            k_pe = ja.rotary(ckv[..., None, r:], positions, cfg.rope_theta,
                             interleaved=True)[:, :, 0]
            return jnp.concatenate([ckv[..., :r], k_pe], -1)
        _patch(undo, ja, 'latent_rows', unnormed)
    elif fault == 'w_uk_transposed':
        # a head's key half read across the heads: kv_b_proj's columns
        # taken as [nope + v, heads] where they lie [heads, nope + v]
        # (the decode steps' absorbed form alone; a prefill expands)
        sound = ja.absorbed_halves

        def across(a, cfg):
            n = cfg.qk_nope_head_dim
            w = a['kv_b_proj.weight'].reshape(
                cfg.kv_lora_rank, -1, cfg.num_heads).swapaxes(1, 2)
            return w[..., :n], sound(a, cfg)[1]
        _patch(undo, ja, 'absorbed_halves', across)
    elif fault == 'bias_in_the_weights':
        def biased(logits, bias, k, scale):
            s = jax.nn.sigmoid(logits.astype(F32)) + bias.astype(F32)
            top_s, top_i = jax.lax.top_k(s, k)
            return top_i, renormalised(top_s, scale)
        _patch(undo, ja, 'sigmoid_top_k', biased)
    elif fault == 'no_route_scale':
        sound = ja.sigmoid_top_k
        _patch(undo, ja, 'sigmoid_top_k', lambda logits, bias, k, scale:
               sound(logits, bias, k, 1.0))
    elif fault == 'experts_outside_the_share_computed':
        # an assignment to an expert held elsewhere is not dropped: it
        # lands on a held one (its index modulo the share)
        sound = ja.chosen_experts

        def everywhere(p, h2, top_i, w, *, held, **kw):
            count = p['gate_proj'].shape[0]
            return sound(p, h2, (top_i - held[0]) % count, w, held=None,
                         **kw)
        _patch(undo, ja, 'chosen_experts', everywhere)
    elif fault == 'shared_expert_left_out':
        sub = ja.sub
        _patch(undo, ja, 'sub', lambda params, prefix: {
            k: jnp.zeros_like(v) for k, v in sub(params, prefix).items()
        } if prefix == 'shared.' else sub(params, prefix))
    else:
        raise ValueError(f'unknown fault {fault!r}')

    def restore():
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)
    return restore
