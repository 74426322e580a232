"""The fc + GELU half of a transformer MLP, shared by the GPT and BERT
blocks.

XLA fuses bias + GELU into the matmul's epilogue on its own.  A
hand-written Pallas matmul+GELU kernel with a recompute-fused backward
was measured on the v5e at 5.2 TFLOP/s against XLA's 11.1 at
[8192, 768] x [768, 3072] bf16 and was deleted: do not write it again.
"""


def mlp_gelu(x, fc, shard_spec=None):
    """x: Tensor [..., H]; fc: a Linear-like Layer with .weight/.bias;
    shard_spec: the activation PartitionSpec for the mesh path (under a
    mesh the tp-sharded column-parallel output is constrained to it)."""
    from ..nn import functional as F
    from ..parallel.api import maybe_shard
    h = fc(x)
    if shard_spec is not None:
        h = maybe_shard(h, shard_spec)   # identity without a mesh
    return F.gelu(h, approximate=True)
