"""paddle_tpu.quantization — QAT + post-training quantization.

Reference analogue:
/root/reference/python/paddle/fluid/contrib/slim/quantization/
(imperative/qat.py:40 ImperativeQuantAware,
post_training_quantization.py PostTrainingQuantization,
quantization_pass.py's fake_quantize_* ops).

TPU-native redesign: no graph passes, no per-op CUDA fake-quant
kernels.  Fake quantization is a pure function with a straight-through
estimator (custom_vjp identity gradient) inserted by WRAPPING layers —
the wrapped model stays an ordinary Layer tree that jit/hapi/
ParallelTrainer compile as usual, and XLA folds the quant-dequant
chains into the surrounding matmuls.  The int8 artifact for inference
is a state_dict of int8 weights + scales.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ..nn.layer.layers import Layer
from ..core.dispatch import apply
from ..tensor._helpers import wrap

__all__ = ['fake_quant', 'FakeQuantAbsMax',
           'FakeQuantMovingAverageAbsMax', 'QuantedLayer',
           'ImperativeQuantAware', 'PostTrainingQuantization',
           'quant_post_dynamic', 'load_quantized_model',
           'Int8DynamicLinear', 'Int4DynamicLinear',
           'quantize_dynamic_int8', 'quantize_dynamic_int4',
           'quantize_for_serving']


def _make_fake_quant():
    """quantize-dequantize with a straight-through gradient."""

    @jax.custom_vjp
    def fq(x, scale, qmax):
        s = jnp.maximum(scale, 1e-8)
        q = jnp.clip(jnp.round(x / s * qmax), -qmax, qmax)
        return q * s / qmax

    def fwd(x, scale, qmax):
        return fq(x, scale, qmax), (x, scale, qmax)

    def bwd(res, g):
        x, scale, qmax = res
        # STE: pass gradients through inside the clip range, zero outside
        s = jnp.maximum(scale, 1e-8)
        inside = (jnp.abs(x) <= s).astype(g.dtype)
        return (g * inside, jnp.zeros_like(scale),
                jnp.zeros_like(qmax))

    fq.defvjp(fwd, bwd)
    return fq


_fq = _make_fake_quant()


def _channel_scale(w, axis, xp=jnp):
    """Per-channel abs-max scale along `axis`, shaped for broadcast
    against `w`.  The SINGLE definition of the channel-wise grid: both
    the QAT fake-quant (training) and the deploy artifact (save) use
    it, so the deployed quantization provably matches what training
    simulated."""
    red = tuple(d for d in range(w.ndim) if d != axis)
    shape = [1] * w.ndim
    shape[axis] = w.shape[axis]
    return xp.maximum(xp.max(xp.abs(w), axis=red),
                      1e-8).reshape(shape)


def fake_quant(x, scale, bits=8):
    """Public fake-quant op: quantize to `bits` and dequantize, with a
    straight-through estimator for training (reference
    fake_quantize_dequantize_abs_max)."""
    qmax = float(2 ** (bits - 1) - 1)
    return apply(lambda v, s: _fq(v, s, jnp.asarray(qmax, v.dtype)),
                 wrap(x), wrap(scale), op_name='fake_quant')


class FakeQuantAbsMax(Layer):
    """Per-tensor dynamic abs-max fake quant (reference
    quantization_pass.py fake_quantize_abs_max)."""

    def __init__(self, bits=8, channel_wise=False, axis=0):
        super().__init__()
        self.bits = bits
        self.channel_wise = channel_wise
        self.axis = axis

    def forward(self, x):
        qmax = float(2 ** (self.bits - 1) - 1)

        def fn(v):
            if self.channel_wise:
                s = _channel_scale(v, self.axis)
            else:
                s = jnp.max(jnp.abs(v))
            return _fq(v, s, jnp.asarray(qmax, v.dtype))

        return apply(fn, wrap(x), op_name='fake_quant_abs_max')


class FakeQuantMovingAverageAbsMax(Layer):
    """Activation fake quant with a moving-average scale (reference
    fake_quantize_moving_average_abs_max): the scale is LEARNED state
    during training and frozen for eval."""

    def __init__(self, bits=8, moving_rate=0.9):
        super().__init__()
        self.bits = bits
        self.moving_rate = moving_rate
        self.scale = self.create_buffer(
            'scale', jnp.asarray([0.0], jnp.float32))

    def forward(self, x):
        qmax = float(2 ** (self.bits - 1) - 1)
        r = self.moving_rate
        training = self.training

        def fn(v, scale):
            cur = jnp.max(jnp.abs(v)).astype(jnp.float32)
            if training:
                new_scale = jnp.where(scale[0] > 0,
                                      r * scale[0] + (1 - r) * cur, cur)
            else:
                new_scale = jnp.where(scale[0] > 0, scale[0], cur)
            out = _fq(v, new_scale.astype(v.dtype),
                      jnp.asarray(qmax, v.dtype))
            return out, new_scale[None]

        out, new_scale = apply(fn, wrap(x), self.scale,
                               op_name='fake_quant_moving_avg')
        if self.training:
            self.scale.value = new_scale.value \
                if hasattr(new_scale, 'value') else new_scale
        return out

    def create_buffer(self, name, value):
        from ..core.tensor import Tensor
        buf = Tensor(value)
        buf.stop_gradient = True
        self.register_buffer(name, buf)
        return buf


class QuantedLayer(Layer):
    """Wrapper installing fake quant on a layer's weight and input —
    the dygraph QuantizedConv2D/QuantizedLinear equivalent (reference
    imperative/quant_layers.py)."""

    def __init__(self, layer, weight_bits=8, act_bits=8,
                 weight_quantize_type='abs_max',
                 activation_quantize_type='moving_average_abs_max',
                 moving_rate=0.9):
        super().__init__()
        self.inner = layer
        channel_wise = weight_quantize_type == 'channel_wise_abs_max'
        # Linear weights are [in, out] -> channel axis 1; Conv [O, I, kh,
        # kw] -> axis 0
        w = getattr(layer, 'weight', None)
        axis = 1 if (w is not None and len(w.shape) == 2) else 0
        self.weight_fq = FakeQuantAbsMax(weight_bits,
                                         channel_wise=channel_wise,
                                         axis=axis)
        if activation_quantize_type == 'moving_average_abs_max':
            self.act_fq = FakeQuantMovingAverageAbsMax(act_bits,
                                                       moving_rate)
        else:
            self.act_fq = FakeQuantAbsMax(act_bits)

    def forward(self, x):
        x = self.act_fq(x)
        inner = self.inner
        w = inner.weight
        orig = w.value
        # fake-quant the weight for this call; restore after (the
        # optimizer keeps training the fp master weight)
        fq_w = self.weight_fq(w)
        w.value = fq_w.value if hasattr(fq_w, 'value') else fq_w
        try:
            out = inner(x)
        finally:
            w.value = orig
        return out


_DEFAULT_QUANTIZABLE = ('Conv2D', 'Linear')


class ImperativeQuantAware:
    """Rewrite a dygraph model's quantizable sublayers in place for QAT
    (reference imperative/qat.py:40)."""

    def __init__(self, quantizable_layer_type=_DEFAULT_QUANTIZABLE,
                 weight_quantize_type='abs_max',
                 activation_quantize_type='moving_average_abs_max',
                 weight_bits=8, activation_bits=8, moving_rate=0.9,
                 **unused):
        self.types = tuple(t if isinstance(t, str) else t.__name__
                           for t in quantizable_layer_type)
        self.wq = weight_quantize_type
        self.aq = activation_quantize_type
        self.wbits = weight_bits
        self.abits = activation_bits
        self.moving_rate = moving_rate

    def quantize(self, model):
        """Swap every matching sublayer for its QuantedLayer wrapper
        in place (the reference mutates the dygraph tree the same way)."""
        self._quantize_tree(model)
        return model

    def _quantize_tree(self, layer):
        for name, child in list(getattr(layer, '_sub_layers',
                                        {}).items()):
            if type(child).__name__ in self.types \
                    and getattr(child, 'weight', None) is not None:
                wrapped = QuantedLayer(
                    child, self.wbits, self.abits, self.wq, self.aq,
                    self.moving_rate)
                layer._sub_layers[name] = wrapped
            else:
                self._quantize_tree(child)

    def save_quantized_model(self, model, path, input_spec=None):
        """Persist int8 weights + scales (the deploy artifact; the
        reference emits a quantized inference Program)."""
        state = {}
        for name, layer in _named_sublayers(model):
            if isinstance(layer, QuantedLayer):
                w = np.asarray(layer.inner.weight.value)
                if layer.weight_fq.channel_wise:
                    # per-channel scales along the SAME axis (and via
                    # the same helper) the QAT fake-quant simulated —
                    # a single per-tensor scale here would deploy
                    # coarser quantization than was trained for
                    scale = _channel_scale(
                        w, layer.weight_fq.axis,
                        xp=np).astype(np.float32)
                else:
                    scale = np.float32(float(np.abs(w).max()) or 1e-8)
                # the artifact's grid must be the one QAT simulated:
                # qmax from the layer's weight_bits, not a fixed 127
                bits = layer.weight_fq.bits
                if bits > 8:
                    raise ValueError(
                        f'cannot store {bits}-bit weights in the int8 '
                        'artifact')
                qmax = float(2 ** (bits - 1) - 1)
                q = np.clip(np.round(w / scale * qmax), -qmax,
                            qmax).astype(np.int8)
                state[f'{name}.qweight'] = q
                state[f'{name}.scale'] = scale
                state[f'{name}.qmax'] = np.float32(qmax)
                act_scale = getattr(layer.act_fq, 'scale', None)
                if act_scale is not None:
                    state[f'{name}.act_scale'] = np.asarray(
                        act_scale.value)
        import pickle
        with open(path + '.quant', 'wb') as f:
            pickle.dump(state, f)
        return state


def _named_sublayers(model):
    """Dotted (name, layer) pairs — the Layer system's own traversal
    (layers.py::named_sublayers), excluding the root."""
    return model.named_sublayers()


class PostTrainingQuantization:
    """PTQ: run calibration batches through the model, record per-layer
    abs-max activation scales, emit int8 weights + scales (reference
    post_training_quantization.py, abs_max algo)."""

    def __init__(self, model, data_loader=None, batch_nums=10,
                 algo='abs_max', quantizable_op_type=_DEFAULT_QUANTIZABLE):
        if algo not in ('abs_max',):
            raise NotImplementedError(f'PTQ algo {algo!r}; abs_max only')
        self.model = model
        self.loader = data_loader
        self.batch_nums = batch_nums
        self.types = tuple(t if isinstance(t, str) else t.__name__
                           for t in quantizable_op_type)
        self._act_scales = {}

    def quantize(self):
        """Calibrate + build the quantized state dict."""
        hooks = []
        for name, layer in _named_sublayers(self.model):
            if type(layer).__name__ in self.types \
                    and getattr(layer, 'weight', None) is not None:
                def make_hook(nm):
                    def hook(layer, inputs):
                        x = inputs[0]
                        v = float(np.abs(np.asarray(
                            x.value if hasattr(x, 'value') else x)).max())
                        self._act_scales[nm] = max(
                            self._act_scales.get(nm, 0.0), v)
                    return hook
                hooks.append(layer.register_forward_pre_hook(
                    make_hook(name)))
        try:
            if self.loader is not None:
                for i, batch in enumerate(self.loader):
                    if i >= self.batch_nums:
                        break
                    xs = batch[0] if isinstance(batch, (list, tuple)) \
                        else batch
                    from ..core.tensor import Tensor
                    self.model(Tensor(jnp.asarray(np.asarray(xs))))
        finally:
            for h in hooks:
                h.remove()
        out = {}
        for name, layer in _named_sublayers(self.model):
            if type(layer).__name__ in self.types \
                    and getattr(layer, 'weight', None) is not None:
                w = np.asarray(layer.weight.value)
                scale = float(np.abs(w).max()) or 1e-8
                out[f'{name}.qweight'] = np.clip(
                    np.round(w / scale * 127), -127, 127).astype(np.int8)
                out[f'{name}.scale'] = np.float32(scale)
                if name in self._act_scales:
                    out[f'{name}.act_scale'] = np.float32(
                        self._act_scales[name])
        return out

    def save_quantized_model(self, save_model_path, **kw):
        state = self.quantize()
        import pickle
        with open(save_model_path + '.quant', 'wb') as f:
            pickle.dump(state, f)
        return state


def quant_post_dynamic(model):
    """Weight-only dynamic quantization: int8 weights + scales, no
    calibration (reference's WeightQuantization.quantize_weight_to_int)."""
    return PostTrainingQuantization(model, data_loader=None).quantize()


class Int8DynamicLinear(Layer):
    """Serving-time nn.Linear replacement that EXECUTES on the MXU's
    native int8 path (ops/int8_matmul.py) — unlike the .quant
    artifact path, which dequantizes back to float at load.  Weights
    stay int8 in HBM (half the bytes of bf16 — the KV-cache decode
    step is weight-bandwidth-bound), activations quantize dynamically
    per call, the dot accumulates in int32.  Inference-only: gradients
    do not flow into the int8 weights."""

    def __init__(self, linear):
        super().__init__()
        from ..core.tensor import Tensor
        from ..ops.int8_matmul import quantize_weight_int8
        w_shape = linear.weight.shape          # [in, out] all variants
        self.in_features = int(w_shape[0])
        self.out_features = int(w_shape[1])
        # quantize on-device: a host round-trip per Linear would
        # copy every weight of the model out and back
        q, scale = quantize_weight_int8(linear.weight.value)
        self.register_buffer('qweight',
                             Tensor(q, stop_gradient=True))
        self.register_buffer('wscale',
                             Tensor(scale, stop_gradient=True))
        self.bias = linear.bias

    def forward(self, x):
        from ..ops.int8_matmul import dynamic_int8_matmul

        def fn(xv, qv, sv, *maybe_b):
            out_dtype = xv.dtype if jnp.issubdtype(
                xv.dtype, jnp.floating) else jnp.bfloat16
            return dynamic_int8_matmul(
                xv, qv, sv, maybe_b[0] if maybe_b else None,
                out_dtype=out_dtype)

        args = [wrap(x), wrap(self.qweight), wrap(self.wscale)]
        if self.bias is not None:
            args.append(wrap(self.bias))
        return apply(fn, *args, op_name='int8_linear')

    def extra_repr(self):
        return f'in={self.in_features}, out={self.out_features}, int8'


class Int4DynamicLinear(Layer):
    """Serving-time nn.Linear replacement on PACKED int4 weights
    (ops/int8_matmul.quantize_weight_int4_packed): two H-rows per
    uint8 in HBM — a QUARTER of bf16's weight bytes on the
    weight-bandwidth-bound decode step — unpacked to int8 in the
    kernel and fed through the same int8 x int8 -> int32 dot as
    :class:`Int8DynamicLinear`.  Coarser grid (qmax=7): gate quality
    per model before shipping (tools/quant_accuracy for the wire;
    eval-set perplexity for PTQ weights).  Inference-only."""

    def __init__(self, linear):
        super().__init__()
        from ..core.tensor import Tensor
        from ..ops.int8_matmul import quantize_weight_int4_packed
        w_shape = linear.weight.shape          # [in, out] all variants
        self.in_features = int(w_shape[0])
        self.out_features = int(w_shape[1])
        packed, scale = quantize_weight_int4_packed(linear.weight.value)
        self.register_buffer('qweight',
                             Tensor(packed, stop_gradient=True))
        self.register_buffer('wscale',
                             Tensor(scale, stop_gradient=True))
        self.bias = linear.bias

    def forward(self, x):
        from ..ops.int8_matmul import dynamic_int4_matmul
        rows = self.in_features

        def fn(xv, qv, sv, *maybe_b):
            out_dtype = xv.dtype if jnp.issubdtype(
                xv.dtype, jnp.floating) else jnp.bfloat16
            return dynamic_int4_matmul(
                xv, qv, sv, rows=rows,
                bias=maybe_b[0] if maybe_b else None,
                out_dtype=out_dtype)

        args = [wrap(x), wrap(self.qweight), wrap(self.wscale)]
        if self.bias is not None:
            args.append(wrap(self.bias))
        return apply(fn, *args, op_name='int4_linear')

    def extra_repr(self):
        return f'in={self.in_features}, out={self.out_features}, int4'


def _quantize_dynamic(model, make_layer, layer_filter=None):
    """Swap every plain nn.Linear sublayer of `model` for
    ``make_layer(sub)``, in place.  Only exact nn.Linear instances are
    swapped — subclasses (tp-sharded parallel linears under a live tp
    axis, already-wrapped QuantedLayers) keep their own math.
    `layer_filter(full_name, layer) -> bool` opts layers out (e.g.
    keep a numerically-sensitive head in bf16).  Returns `model`."""
    from ..nn import Linear
    from ..distributed import env as dist_env
    from ..distributed.fleet.meta_parallel import (ColumnParallelLinear,
                                                   RowParallelLinear)

    # tp-sharded parallel linears are functionally plain Linears when
    # no tp mesh axis is live (single-chip serving — the decode A/B
    # target); with a real tp axis their weights are sharded and the
    # per-shard quantization story is different, so they are skipped
    mesh = dist_env.get_mesh()
    tp_live = mesh is not None and 'tp' in mesh.axis_names \
        and mesh.shape['tp'] > 1
    swappable = (Linear,) if tp_live else \
        (Linear, ColumnParallelLinear, RowParallelLinear)

    def walk(layer, prefix=''):
        n = 0
        for name, sub in list(layer._sub_layers.items()):
            full = f'{prefix}.{name}' if prefix else name
            if type(sub) in swappable and (layer_filter is None
                                           or layer_filter(full, sub)):
                layer._sub_layers[name] = make_layer(sub)
                n += 1
            elif isinstance(sub, QuantedLayer):
                # QuantedLayer.forward re-reads inner.weight for fake
                # quant — swapping its inner Linear would break it;
                # QAT models export through the .quant artifact path
                continue
            else:
                n += walk(sub, full)
        return n

    if walk(model) == 0:
        hint = ''
        if type(model) in swappable:
            hint = (' — the ROOT layer is itself a quantizable '
                    'Linear, but an in-place swap needs a parent: '
                    'wrap it (e.g. nn.Sequential(model)) and '
                    'quantize that')
        raise ValueError('no quantizable Linear sublayers found'
                         + hint)
    return model


def quantize_dynamic_int8(model, layer_filter=None):
    """Swap every plain nn.Linear sublayer of `model` for an
    Int8DynamicLinear, in place (the executing analog of
    quant_post_dynamic; reference serving runs int8 through
    PaddleSlim + TensorRT kernels, here it is one int8 dot_general on
    the MXU).  Typical decode use:

        model.eval()
        quantize_dynamic_int8(model)
        model.generate(ids, max_new_tokens=128)   # one XLA module
    """
    return _quantize_dynamic(model, Int8DynamicLinear, layer_filter)


def quantize_dynamic_int4(model, layer_filter=None):
    """int4 twin of :func:`quantize_dynamic_int8`: packed nibbles in
    HBM, unpacked in the kernel (ops/int8_matmul.dynamic_int4_matmul).
    A quarter of bf16's weight bytes; coarser grid — measure quality
    before shipping."""
    return _quantize_dynamic(model, Int4DynamicLinear, layer_filter)


_SERVING_MODES = {'int8': quantize_dynamic_int8,
                  'int4': quantize_dynamic_int4}


def quantize_for_serving(model, mode='int8', layer_filter=None):
    """Weight-only PTQ of a serving model, in place — the
    ``ServeConfig(quantize=...)`` entry point.  ``mode`` is 'int8'
    (Int8DynamicLinear) or 'int4' (packed Int4DynamicLinear); every
    decode then reads half-width (or quarter-width) weights from HBM
    through the MXU's native int8 path.  Activations stay dynamic
    per-call; the KV cache and embeddings keep their dtype.  Returns
    `model`."""
    fn = _SERVING_MODES.get(mode)
    if fn is None:
        raise ValueError(
            f'quantize_for_serving mode {mode!r}: expected one of '
            f'{sorted(_SERVING_MODES)}')
    model.eval()
    fn(model, layer_filter)
    # the swap is IRREVERSIBLE (float weights are dropped): mark the
    # model so a ServingEngine whose config declares a different
    # quantize mode refuses instead of compiling a mis-keyed surface
    model._ptq_mode = mode
    return model


def load_quantized_model(model, path):
    """Load a `.quant` artifact back onto `model`: int8 weights
    dequantize through their scales into the live fp parameters —
    weight-only int8 inference (the reference's quantized inference
    Program reads the same scales from its ProgramDesc attrs).

    `model` must have the same layer names as the saver (wrapped
    QuantedLayers load into `<name>.inner`)."""
    import pickle
    with open(path + '.quant', 'rb') as f:
        state = pickle.load(f)
    layers = dict(_named_sublayers(model))
    n = 0
    for key, q in state.items():
        if not key.endswith('.qweight'):
            continue
        name = key[:-len('.qweight')]
        scale = state[name + '.scale']
        target = layers.get(name)
        if target is None:
            raise KeyError(f'{name!r} not found in model')
        if isinstance(target, QuantedLayer):
            target = target.inner
        # scale is a scalar (per-tensor) or a broadcast-shaped vector
        # (channel_wise_abs_max: one scale per output channel); qmax
        # defaults to 127 for artifacts predating the qmax field
        qmax = float(state.get(name + '.qmax', 127.0))
        w = (np.asarray(q, np.float32)
             * np.asarray(scale, np.float32) / qmax)
        target.weight.value = jnp.asarray(w, target.weight.value.dtype)
        n += 1
    if n == 0:
        raise ValueError(f'no quantized weights in {path}.quant')
    return model
