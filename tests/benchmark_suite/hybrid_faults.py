"""Faults planted in the program's hybrid Mamba-2 / attention decoder
(`paddle_tpu/models/granite_hybrid.py`, `paddle_tpu/ops/ssm.py` and the
state cache it is served by), for the tests on the CPU
(test_benchmark_serve_hybrid.py) and, at the cell's own size, on the
chip (chip_control_hybrid.py).  `plant(fault)` patches the program
where it calls or reads the part and returns the call that undoes it;
the reference is never touched.

`FAULTS` every probe has to fail, on the CPU and on the chip.
`CHIP_CONTROLS` are ways of computing a part below the precision the
configuration states that a TPU may or may not take (on the CPU each
is exact): the chip control reads them."""
FAULTS = ('pads_reach_the_state', 'prefill_writes_the_neighbour_slot',
          'd_skip_missing', 'norm_before_the_gate', 'dt_bias_missing',
          'rotary_on_the_attention_layers', 'scale_of_one_over_sqrt_d',
          'residual_multiplier_missing', 'conv_window_in_bfloat16')
CHIP_CONTROLS = ('conv_step_as_a_default_precision_dot',)

_ABSENT = object()


def _patch(undo, obj, name, value):
    # a class attribute the instances set themselves is absent from the
    # class: a property put there reads before the instance's own value
    undo.append((obj, name, vars(obj).get(name, _ABSENT)))
    setattr(obj, name, value)


def plant(fault):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import granite_hybrid as gh
    from paddle_tpu.models.decoder_parts import rms_norm
    from paddle_tpu.ops import ssm
    from paddle_tpu.serving.kv_cache import RecurrentStateCache
    F32 = jnp.float32
    undo = []

    def mamba_params(change):
        """`sub` with the Mamba mixers' tensors changed by `change`."""
        sound = gh.sub

        def changed(params, prefix):
            out = sound(params, prefix)
            return change(out) if prefix == 'mamba.' else out
        _patch(undo, gh, 'sub', changed)

    if fault == 'pads_reach_the_state':
        # every position of the bucket feeds the state and the conv's
        sound = gh.scan_inputs
        _patch(undo, gh, 'scan_inputs', lambda p, h, cfg, lengths:
               sound(p, h, cfg, None))
    elif fault == 'prefill_writes_the_neighbour_slot':
        sound = RecurrentStateCache.prefill_where

        def shifted(self, seq_ids, rows, bucket):
            return (sound(self, seq_ids, rows, bucket) + 1) % self.slots
        _patch(undo, RecurrentStateCache, 'prefill_where', shifted)
    elif fault == 'd_skip_missing':
        mamba_params(lambda p: {**p, 'D': jnp.zeros_like(p['D'])})
    elif fault == 'norm_before_the_gate':
        _patch(undo, gh, 'gated_norm', lambda y, z, weight, eps:
               rms_norm(y, weight, eps) * jax.nn.silu(z))
    elif fault == 'dt_bias_missing':
        mamba_params(lambda p: {**p, 'dt_bias': jnp.zeros_like(
            p['dt_bias'])})
    elif fault == 'rotary_on_the_attention_layers':
        _patch(undo, gh.GraniteHybridConfig, 'position_embedding_type',
               property(lambda cfg: 'rope'))
    elif fault == 'scale_of_one_over_sqrt_d':
        # the kernels' own 1 / sqrt(head_dim) for attention_multiplier
        _patch(undo, gh.GraniteHybridConfig, 'q_scale', property(
            lambda cfg: 1.0))
    elif fault == 'residual_multiplier_missing':
        _patch(undo, gh.GraniteHybridConfig, 'residual_multiplier',
               property(lambda cfg: 1.0))
    elif fault == 'conv_window_in_bfloat16':
        # a decode step's conv over its window rounded to bfloat16 (what
        # a dot at the default precision does on a TPU's MXU), by
        # reduce_precision: a TPU's compiler drops a round trip through
        # a bfloat16 array, and keeps this
        sound = ssm.conv_step

        def rounded(x, state, weight, bias):
            def bf16(v):
                return jax.lax.reduce_precision(v.astype(F32), 8, 7)
            return sound(bf16(x), bf16(state), weight, bias)
        _patch(undo, ssm, 'conv_step', rounded)
    elif fault == 'conv_step_as_a_default_precision_dot':
        # a decode step's taps as one dot at the default precision
        def dot(x, state, weight, bias):
            with jax.named_scope('ssm.conv'):
                window = jnp.concatenate([state.astype(F32),
                                          x.astype(F32)[:, None]], axis=1)
                y = jnp.einsum('rkc,kc->rc', window, weight.astype(F32))
                return jax.nn.silu(y + bias.astype(F32)), window[:, 1:]
        _patch(undo, ssm, 'conv_step', dot)
    else:
        raise ValueError(f'unknown fault {fault!r}')

    def restore():
        for obj, name, value in reversed(undo):
            if value is _ABSENT:
                delattr(obj, name)
            else:
                setattr(obj, name, value)
    return restore
