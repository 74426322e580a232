"""Pallas TPU kernels (reference analogue: the reference's fused CUDA
ops under paddle/fluid/operators/fused/).  Each op auto-falls back to a
jnp reference implementation off-TPU or for unsupported shapes."""
from .flash_attention import flash_attention  # noqa: F401
from .fused_norm import fused_layer_norm  # noqa: F401
from .fused_softmax import fused_softmax  # noqa: F401

__all__ = ['flash_attention', 'fused_layer_norm', 'fused_softmax']
