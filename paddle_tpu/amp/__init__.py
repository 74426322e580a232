"""paddle_tpu.amp — automatic mixed precision.

Reference analogue: /root/reference/python/paddle/amp/auto_cast.py and
grad_scaler.py (which wrap the C++ dygraph tracer's AMP lists, see
paddle/fluid/imperative/amp_auto_cast.cc).  TPU-native: the preferred
low-precision dtype is bfloat16 — same exponent range as float32, so no
loss scaling is *needed*; GradScaler is kept fully operative anyway for
float16 use and API parity.  Casting happens at the single eager
dispatch choke point (core/dispatch.set_amp_hook) instead of per-op C++
wrappers, and the compiled path (paddle_tpu.jit) applies the same policy
during tracing so the casts land inside the XLA module where they fuse
into the matmuls for free.
"""
import contextlib

import jax.numpy as jnp

from ..core import dispatch
from ..core.tensor import Tensor
from ..core.dtype import convert_dtype

__all__ = ['auto_cast', 'amp_guard', 'decorate', 'amp_decorate',
           'GradScaler', 'WHITE_LIST', 'BLACK_LIST', 'audit']

# Ops whose FLOPs dominate and which the MXU runs natively in bf16.
# Mirrors the reference's white list {conv2d, matmul, mul} plus our op
# names for the same computations.
WHITE_LIST = frozenset({
    'matmul', 'bmm', 'mv', 'dot', 'mul', 'linear', 'conv1d', 'conv2d',
    'conv3d', 'conv2d_transpose', 'conv1d_transpose', 'conv3d_transpose',
    'einsum', 'addmm', 'flash_attention',
})

# Numerically-sensitive ops kept in float32 (reference black list:
# exp/log/softmax/cross_entropy/... — reductions and transcendentals).
BLACK_LIST = frozenset({
    'exp', 'expm1', 'log', 'log2', 'log10', 'log1p', 'pow', 'square',
    'sqrt', 'rsqrt', 'reciprocal', 'softmax', 'log_softmax',
    'cross_entropy', 'softmax_with_cross_entropy', 'nll_loss',
    'binary_cross_entropy', 'bce_with_logits',
    'kl_div', 'cosh', 'sinh', 'tan', 'mean', 'sum', 'norm', 'dist',
    'reduce_mean', 'reduce_sum', 'cumsum', 'logsumexp', 'softplus',
    'erf', 'erfinv', 'lgamma', 'digamma', 'cross_entropy_loss',
    # loss heads compute in f32 even when the step runs under an O1/O2
    # autocast (ParallelTrainer wraps loss_fn in the forward's policy):
    # each dispatches as ONE op, so without this a bf16 forward output
    # would drag the f32 labels down via the gray/O2 rules
    'mse_loss', 'l1_loss', 'square_error_cost', 'smooth_l1_loss',
    'margin_ranking_loss', 'hinge_embedding_loss',
    'cosine_embedding_loss', 'log_loss', 'ctc_loss',
    'sigmoid_focal_loss',
})

# Normalization ops manage their own mixed precision: the functionals in
# nn/functional/norm.py compute statistics with float32 accumulation and
# apply the normalization in the input dtype (folded per-channel
# scale/shift that XLA fuses into the producing conv/matmul epilogue).
# Casting their inputs here — either direction — would only add HBM
# traffic: an f32 upcast doubles the activation bytes saved for backward
# (this was the round-1 ResNet bottleneck: the step was HBM-bound with
# every BN materializing f32 copies), while a bf16 downcast would round
# the f32 scale/shift parameters for no gain.
KEEP_LIST = frozenset({
    'layer_norm', 'batch_norm', 'instance_norm', 'group_norm',
})

_FLOATS = (jnp.float32, jnp.float16, jnp.bfloat16, jnp.float64)


class _AmpState:
    def __init__(self):
        self.enabled = False
        self.level = 'O1'
        self.dtype = jnp.bfloat16
        self.white = WHITE_LIST
        self.black = BLACK_LIST


_state = _AmpState()


def _is_float(v):
    return v.dtype in _FLOATS


def _cast_all(vals, dtype):
    return [v.astype(dtype) if _is_float(v) and v.dtype != dtype else v
            for v in vals]


def _amp_hook(op_name, vals):
    if not _state.enabled:
        return vals
    if (op_name in KEEP_LIST and op_name not in _state.black
            and op_name not in _state.white):  # custom lists still win
        return vals
    if op_name in _state.black:
        return _cast_all(vals, jnp.float32)
    if _state.level == 'O2':
        # pure-low-precision mode: everything not blacklisted runs low
        return _cast_all(vals, _state.dtype)
    if op_name in _state.white:
        return _cast_all(vals, _state.dtype)
    # O1 gray ops: if any input is already low precision, follow it —
    # keeps elementwise chains fused in bf16 between matmuls.
    if any(_is_float(v) and v.dtype == _state.dtype for v in vals):
        return _cast_all(vals, _state.dtype)
    return vals


dispatch.set_amp_hook(_amp_hook)


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level='O1', dtype='bfloat16'):
    """Context manager enabling mixed precision (reference:
    python/paddle/amp/auto_cast.py::amp_guard)."""
    if level not in ('O0', 'O1', 'O2'):
        raise ValueError(f"level must be O0/O1/O2, got {level}")
    prev = (_state.enabled, _state.level, _state.dtype, _state.white,
            _state.black)
    _state.enabled = bool(enable) and level != 'O0'
    _state.level = level
    _state.dtype = convert_dtype(dtype) or jnp.bfloat16
    white, black = set(WHITE_LIST), set(BLACK_LIST)
    if custom_white_list:
        white |= set(custom_white_list)
        black -= set(custom_white_list)
    if custom_black_list:
        black |= set(custom_black_list)
        white -= set(custom_black_list)
    _state.white, _state.black = frozenset(white), frozenset(black)
    try:
        yield
    finally:
        (_state.enabled, _state.level, _state.dtype, _state.white,
         _state.black) = prev


amp_guard = auto_cast


def is_amp_enabled():
    return _state.enabled


def amp_state():
    """(enabled, level, dtype) — read by paddle_tpu.jit so compiled
    traces apply the same policy."""
    return _state


def audit():
    """Eager mixed-precision audit (paddle_tpu.analysis.amp_audit):

        with amp.audit() as a, amp.auto_cast():
            model(x)
        print(a.report())   # amp-promotion findings: f32 operands the
                            # hook re-casts every step

    The jaxpr-level twin (f32 creep inside compiled steps) runs via
    analysis.lint / to_static(check=...) / Model.prepare(lint=...)."""
    from ..analysis import amp_audit
    return amp_audit()


def decorate(models, optimizers=None, level='O1', dtype='bfloat16',
             master_weight=None, save_dtype=None):
    """Reference: paddle.amp.decorate.  O2 casts model params to the low
    dtype (master weights stay fp32 inside the optimizer when
    multi_precision is on)."""
    single_model = not isinstance(models, (list, tuple))
    model_list = [models] if single_model else list(models)
    if level == 'O2':
        target = convert_dtype(dtype) or jnp.bfloat16
        for m in model_list:
            for p in m.parameters():
                if _is_float(p.value):
                    p.value = p.value.astype(target)
    if optimizers is None:
        return models if single_model else model_list
    return (models if single_model else model_list), optimizers


amp_decorate = decorate


class GradScaler:
    """Reference: python/paddle/amp/grad_scaler.py.  Loss-scaling for
    float16; with bfloat16 (TPU default) scaling is a no-op numerically
    but the dynamic-scale state machine still runs for API parity and
    the non-finite-gradient *skip* remains active as a NaN guard."""

    def __init__(self, enable=True, init_loss_scaling=2.**15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every_n_steps = incr_every_n_steps
        self._decr_every_n = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._unscaled = False

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_init_loss_scaling(self):
        return self._scale

    def scale(self, var):
        if not self._enable:
            return var
        return var * self._scale

    def unscale_(self, optimizer):
        if not self._enable or self._unscaled:
            return
        self._unscaled = True
        params = optimizer._params
        inv = 1.0 / self._scale
        found = False
        for p in params:
            if p._grad is not None:
                g = p._grad * inv
                finite = bool(jnp.isfinite(g).all())
                found = found or not finite
                p._grad = g
        self._found_inf = found

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self.update()

    def minimize(self, optimizer, scaled_loss):
        # reference signature: scaler.minimize(opt, scaled) after
        # scaled.backward(); scaled_loss itself is unused here.
        self.step(optimizer)

    def update(self):
        if not (self._enable and self._dynamic):
            return
        self._unscaled = False
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every_n:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every_n_steps:
                self._scale *= self._incr_ratio
                self._good_steps = 0
        self._found_inf = False

    def state_dict(self):
        return {'scale': self._scale, 'incr_ratio': self._incr_ratio,
                'decr_ratio': self._decr_ratio,
                'incr_every_n_steps': self._incr_every_n_steps,
                'decr_every_n_nan_or_inf': self._decr_every_n,
                'good_steps': self._good_steps,
                'bad_steps': self._bad_steps,
                'use_dynamic_loss_scaling': self._dynamic}

    def load_state_dict(self, state):
        self._scale = state['scale']
        self._incr_ratio = state['incr_ratio']
        self._decr_ratio = state['decr_ratio']
        self._incr_every_n_steps = state['incr_every_n_steps']
        self._decr_every_n = state['decr_every_n_nan_or_inf']
        self._good_steps = state['good_steps']
        self._bad_steps = state['bad_steps']
        self._dynamic = state['use_dynamic_loss_scaling']
