"""GPipe pipeline parallelism over the `pp` mesh axis.

Reference analogue: fleet's pipeline_optimizer + meta_parallel/
pipeline_parallel.py (section programs + P2P sends over NCCL).
TPU-native redesign: stages are the SAME jitted block function applied
to a pp-stacked parameter pytree (transformer stacks are homogeneous, so
one stage = a slice of blocks); microbatch activations rotate stage to
stage with `lax.ppermute` inside `shard_map`, and the whole GPipe
schedule — fill, steady state, drain — is one `lax.scan` the compiler
pipelines over ICI.  Backward flows through the same ppermutes reversed
(XLA transposes them automatically), giving 1F1B-style overlap without
hand-written P2P kernels.

Schedule (S stages, M microbatches, T = M + S - 1 ticks):
tick t: stage s computes microbatch (t - s) if 0 <= t - s < M.
Stage 0 injects microbatch t; stage S-1 emits finished outputs.
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ['gpipe', 'gpipe_spmd', 'PipelineLayerModule']


def gpipe(stage_params, x_mb, stage_fn, axis_name):
    """Run inside shard_map: `stage_params` is THIS stage's param slice
    (leading pp dim stripped to 1 locally), `x_mb` is [M, mb, ...] input
    microbatches (only stage 0's copy is consumed).

    Returns [M, mb, ...] outputs (only stage S-1's copy is meaningful).
    """
    sp = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    ticks = m + sp - 1
    # rotate activations stage s -> s+1 (ring; the wrap-around edge
    # carries junk that the validity masking ignores)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    params_local = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    out_struct = jax.eval_shape(
        stage_fn, params_local,
        jax.tree_util.tree_map(lambda a: a[0], x_mb))
    zero_out = jnp.zeros(out_struct.shape, out_struct.dtype)

    def tick(carry, t):
        prev_act, outputs = carry
        mb_idx = t - rank
        valid = (mb_idx >= 0) & (mb_idx < m)
        # stage 0 reads its own microbatch; others read the rotated
        # activation from the previous stage
        my_in = jax.lax.cond(
            rank == 0,
            lambda: jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(mb_idx, 0, m - 1), 0, keepdims=False),
            lambda: prev_act)
        y = stage_fn(params_local, my_in)
        y = jnp.where(valid, y, zero_out)
        # last stage records finished microbatches
        outputs = jax.lax.cond(
            (rank == sp - 1) & valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(mb_idx, 0, m - 1), 0),
            lambda o: o,
            outputs)
        # ship activations to the next stage for tick t+1
        nxt = jax.lax.ppermute(y, axis_name, perm)
        return (nxt, outputs), None

    init = (zero_out,
            jnp.zeros((m,) + zero_out.shape, zero_out.dtype))
    (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
    return outputs


def gpipe_spmd(stacked_params, x, stage_fn, mesh, num_microbatches,
               pp_axis='pp', batch_axes=()):
    """jit-level wrapper.  `stacked_params`: pytree whose leaves have a
    leading dim = pp size (stage-major).  `x`: [B, ...] global batch,
    split into `num_microbatches` along dim 0.  `stage_fn(params, x)`
    applies ONE stage.  Returns [B, ...] outputs from the last stage
    (replicated on pp)."""
    sp = dict(mesh.shape)[pp_axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        assert leaf.shape[0] == sp, (
            f'stacked params lead dim {leaf.shape[0]} != pp size {sp}; '
            f'fold extra stages into stage_fn (stages-per-device > 1)')
    b = x.shape[0]
    assert b % num_microbatches == 0, (b, num_microbatches)
    x_mb = x.reshape((num_microbatches, b // num_microbatches)
                     + x.shape[1:])

    p_spec = P(pp_axis)

    def run(params, xmb):
        out = gpipe(params, xmb, stage_fn=stage_fn, axis_name=pp_axis)
        return out[None]  # per-stage leading dim; only stage S-1 is real

    from jax import shard_map
    out = shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: p_spec,
                                         stacked_params), P()),
        out_specs=P(pp_axis),
        check_vma=False)(stacked_params, x_mb)
    out_mb = out[sp - 1]  # last stage's buffer
    return out_mb.reshape((b,) + out_mb.shape[2:])


class PipelineLayerModule:
    """Generic pipeline adapter for fleet's PipelineLayer — the engine
    behind the reference idiom ``PipelineLayer(descs, num_stages=S)`` +
    ``fleet.distributed_model`` (reference: meta_parallel/pp_layers.py
    feeding pipeline_parallel.py's schedule).

    Heterogeneous stages are routed with ``lax.switch`` on the pp
    coordinate inside the 1F1B engine; every device therefore carries a
    replicated copy of ALL stages' parameters (correctness-first
    fallback — the flagship memory-efficient path stacks homogeneous
    blocks P('pp'), see models/gpt_pipe.py).  Constraints: activations
    entering/leaving every stage share one shape/dtype (the microbatch
    input's), and tp must be 1 (stage compute is tp-replicated here, so
    a tp-psum of grads would double count).
    """

    def __init__(self, pipe_layer, mesh, loss_fn=None, tp_axis='tp'):
        assert dict(mesh.shape).get(tp_axis, 1) == 1, (
            'PipelineLayerModule requires tp==1; use a model-specific '
            'pipeline module (e.g. GPTPipeModule) for tp x pp')
        self.layer = pipe_layer
        self.mesh = mesh
        self.S = pipe_layer.num_stages
        self.loss_fn = loss_fn or pipe_layer.loss_fn
        assert self.loss_fn is not None, 'PipelineLayer needs a loss_fn'
        # per-stage functional param trees, all pp-replicated
        shared = {}
        for s in range(self.S):
            sp = {}
            for li, sub in enumerate(pipe_layer.stage_layers(s)):
                params, buffers = sub.functional_state()
                assert not buffers, (
                    'pipeline stages with buffers (BN running stats) '
                    'are not supported in the compiled pipeline step')
                sp[str(li)] = params
            shared[f'stage{s}'] = sp
        self.params = {'shared': shared, 'stages': {}}
        self.stage_specs = {}

    def restore(self, params):
        for s in range(self.S):
            sp = params['shared'][f'stage{s}']
            for li, sub in enumerate(self.layer.stage_layers(s)):
                sub.load_functional_state(sp[str(li)], {})

    def _apply_stage(self, shared, s, x):
        from ..jit import functional_call
        out = x
        for li, sub in enumerate(self.layer.stage_layers(s)):
            out, _ = functional_call(
                sub, shared[f'stage{s}'][str(li)], {}, (out,),
                training=True)
        return out

    def first_fn(self, shared, x_1mb):
        """The raw microbatch IS the pipeline activation."""
        del shared
        return x_1mb

    def stage_fn(self, shared, stage_p, x, rank):
        del stage_p
        branches = [functools.partial(self._apply_stage, shared, s)
                    for s in range(self.S)]
        return jax.lax.switch(jnp.clip(rank, 0, self.S - 1), branches, x)

    def last_fn(self, shared, y, labels_1mb):
        del shared
        loss = self.loss_fn(y, labels_1mb)
        val = getattr(loss, 'value', loss)
        return jnp.mean(val).astype(jnp.float32)
