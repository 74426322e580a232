"""1F1B pipeline-parallel training engine over the `pp` mesh axis.

Reference analogue:
/root/reference/python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:43
(``PipelineParallel._forward_backward_pipeline``: the 1F1B schedule with
NCCL p2p send/recv between stage ranks) together with
meta_optimizers/pipeline_optimizer.py (program section cut).  TPU-native
redesign: instead of per-rank Python processes exchanging tensors, the
ENTIRE schedule — warmup forwards, steady-state 1F1B interleave,
cooldown backwards — is ONE ``lax.scan`` inside ONE ``shard_map`` over
the (pp, dp, tp) mesh; stage hand-offs are ``lax.ppermute`` ring hops
over ICI and the backward is hand-rolled with ``jax.vjp`` per tick.

Schedule (S stages, M microbatches, T = 2M + 2S - 2 ticks):

    forward  of microbatch m on stage s at tick  2m + s
    backward of microbatch m on stage s at tick  2m + 2S - 1 - s

F-ticks and B-ticks have opposite parity on every device, so each
device does at most one unit of work per tick and alternates F/B in
steady state — the 1F1B order.  A stage holds at most S - s in-flight
microbatch *inputs* (O(S) live activations, not GPipe's O(M)); the
backward tick recomputes the stage forward from the stashed input
(activation recompute, the reference's recompute+pipeline composition).

Non-homogeneous stages: ``first_fn`` (e.g. token+position embedding)
runs only on stage 0, ``last_fn`` (e.g. final LN + LM head + loss) only
on stage S-1, both gated by ``lax.cond`` on the pp coordinate; their
parameters travel in the ``shared`` pytree, replicated over pp, and
their gradients are psum'd over pp (so weights tied between first and
last stage — GPT's embedding/LM head — accumulate both contributions
for free).

Tensor-parallel composition: stage parameters may carry 'tp' in their
PartitionSpec; the stage function is then responsible for its own
``lax.psum(..., 'tp')`` after row-parallel matmuls (see
models/gpt_pipe.py).  Gradients of tp-*replicated* leaves are psum'd
over tp here, driven by whether each leaf's spec mentions the tp axis.
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ['pipeline_value_and_grad']


def _spec_mentions(spec, axis):
    for part in spec:
        if part == axis:
            return True
        if isinstance(part, (tuple, list)) and axis in part:
            return True
    return False


def pipeline_value_and_grad(shared, stages, ids_mb, labels_mb, *, mesh,
                            first_fn, stage_fn, last_fn, stage_specs,
                            pp_axis='pp', dp_axis='dp', tp_axis='tp',
                            ep_axis='ep', with_finite=False):
    """Compute (mean loss, (d_shared, d_stages)) with 1F1B pipelining.

    `with_finite=True` additionally returns a replicated boolean `ok`:
    every microbatch loss was finite (checked PER MICROBATCH inside the
    schedule, on the last stage, at the tick that produced it) AND the
    reduced gradients are finite.  The reduction is folded into the
    same XLA module — nan_guard under pipeline parallelism costs no
    extra dispatch, and only the one boolean crosses to the host.

    shared      : pytree of pp-replicated params (embedding, final LN…).
    stages      : pytree whose leaves are stage-major [S, ...].
    ids_mb      : [M, B_mb, ...] inputs (microbatch-major).
    labels_mb   : [M, B_mb, ...] labels, same layout.
    first_fn(shared, ids_1mb)            -> x0 [mb, ...] float
    stage_fn(shared, stage_p, x, rank)   -> y  (same shape/dtype as x0;
                  rank is the traced pp coordinate — heterogeneous
                  engines lax.switch on it, homogeneous ones ignore it)
    last_fn(shared, y, labels_1mb)       -> scalar per-microbatch loss
    stage_specs : pytree matching `stages` of GLOBAL PartitionSpecs
                  (leading 'pp' + optional 'tp' dims).
    """
    shape = dict(mesh.shape)
    S = shape.get(pp_axis, 1)
    dp = shape.get(dp_axis, 1)
    tp = shape.get(tp_axis, 1)
    ep = shape.get(ep_axis, 1)
    M = ids_mb.shape[0]
    ticks = 2 * M + 2 * S - 2
    perm_dn = [(i, (i + 1) % S) for i in range(S)]   # acts: s -> s+1
    perm_up = [(i, (i - 1) % S) for i in range(S)]   # grads: s -> s-1

    def worker(shared, stages_l, ids, labels):
        # stages_l leaves arrive as [1, ...] local slices — strip pp dim
        stage_p = jax.tree_util.tree_map(lambda a: a[0], stages_l)
        rank = jax.lax.axis_index(pp_axis)
        is_first = rank == 0
        is_last = rank == S - 1

        def full_stage(shared, stage_p, act_in, m):
            """One stage's complete forward for microbatch m: gated
            first_fn on stage 0, blocks, gated last_fn on stage S-1.
            Returns (activation to ship, per-mb loss)."""
            ids_1 = jax.lax.dynamic_index_in_dim(ids, m, 0, keepdims=False)
            lbl_1 = jax.lax.dynamic_index_in_dim(labels, m, 0,
                                                 keepdims=False)
            x = jax.lax.cond(
                is_first,
                lambda: first_fn(shared, ids_1).astype(act_in.dtype),
                lambda: act_in)
            y = stage_fn(shared, stage_p, x, rank)
            loss = jax.lax.cond(
                is_last,
                lambda: last_fn(shared, y, lbl_1).astype(jnp.float32),
                lambda: jnp.float32(0.0))
            return y, loss

        # activation template (shape of what flows between stages)
        x0_shape = jax.eval_shape(
            lambda sh, i: first_fn(sh, i[0]), shared, ids)
        act_zero = jnp.zeros(x0_shape.shape, x0_shape.dtype)
        d_sh0 = jax.tree_util.tree_map(jnp.zeros_like, shared)
        d_st0 = jax.tree_util.tree_map(jnp.zeros_like, stage_p)
        nstash = min(S, M)
        stash0 = jnp.zeros((nstash,) + act_zero.shape, act_zero.dtype)

        def tick(carry, t):
            (act_in, grad_in, stash, d_sh, d_st, loss_acc,
             nbad) = carry
            tf = t - rank
            do_f = (tf >= 0) & (tf < 2 * M) & (tf % 2 == 0)
            m_f = jnp.clip(tf // 2, 0, M - 1)
            tb = t - (2 * S - 1 - rank)
            do_b = (tb >= 0) & (tb < 2 * M) & (tb % 2 == 0)
            m_b = jnp.clip(tb // 2, 0, M - 1)

            def fwd(op):
                act_in, stash, loss_acc, nbad = op
                y, l = full_stage(shared, stage_p, act_in, m_f)
                stash = jax.lax.dynamic_update_index_in_dim(
                    stash, act_in, m_f % nstash, 0)
                # per-microbatch health, folded into the schedule: l
                # is this microbatch's loss on the last stage (0.0 —
                # finite — elsewhere), so nbad counts exactly the
                # non-finite microbatches
                nbad = nbad + (~jnp.isfinite(l)).astype(jnp.int32)
                return y, stash, loss_acc + l, nbad

            act_out, stash, loss_acc, nbad = jax.lax.cond(
                do_f, fwd, lambda op: op,
                (act_in, stash, loss_acc, nbad))

            def bwd(op):
                grad_in, d_sh, d_st = op
                x_saved = jax.lax.dynamic_index_in_dim(
                    stash, m_b % nstash, 0, keepdims=False)
                _, vjp_fn = jax.vjp(
                    lambda sh, sp, a: full_stage(sh, sp, a, m_b),
                    shared, stage_p, x_saved)
                # last stage's shipped activation is unused downstream;
                # its cotangent is zero and the loss seed is 1.0
                dy = jnp.where(is_last, 0.0, 1.0) * grad_in
                dl = jnp.where(is_last, 1.0, 0.0).astype(jnp.float32)
                g_sh, g_st, dx = vjp_fn((dy, dl))
                d_sh = jax.tree_util.tree_map(jnp.add, d_sh, g_sh)
                d_st = jax.tree_util.tree_map(jnp.add, d_st, g_st)
                return dx, d_sh, d_st

            dx_out, d_sh, d_st = jax.lax.cond(
                do_b, bwd, lambda op: op, (grad_in, d_sh, d_st))

            # ring hops: activations ride down, gradients ride up; junk
            # travels on idle edges and is masked by the schedule
            act_nxt = jax.lax.ppermute(act_out, pp_axis, perm_dn)
            grad_nxt = jax.lax.ppermute(dx_out, pp_axis, perm_up)
            return (act_nxt, grad_nxt, stash, d_sh, d_st, loss_acc,
                    nbad), None

        init = (act_zero, act_zero, stash0, d_sh0, d_st0,
                jnp.float32(0.0), jnp.int32(0))
        (_, _, _, d_sh, d_st, loss_acc, nbad), _ = jax.lax.scan(
            tick, init, jnp.arange(ticks))

        # loss lives on stage S-1 only; total over pp, mean over M, dp
        loss = jax.lax.psum(loss_acc, pp_axis) / M
        if dp > 1:
            loss = jax.lax.pmean(loss, dp_axis)
        scale = 1.0 / (M * dp)
        d_sh = jax.tree_util.tree_map(lambda g: g * scale, d_sh)
        d_st = jax.tree_util.tree_map(lambda g: g * scale, d_st)
        # shared params: stage 0 (embedding) and stage S-1 (head)
        # contribute from different pp ranks — total over pp (this is
        # also what ties wte's embedding+head gradients together)
        d_sh = jax.lax.psum(d_sh, pp_axis)
        if dp > 1:
            d_sh = jax.lax.psum(d_sh, dp_axis)
            d_st = jax.lax.psum(d_st, dp_axis)
        # Model-parallel axes (tp: Megatron row/col split; ep: expert
        # shards).  Inside shard_map, the hand-rolled jax.vjp transposes
        # the stage_fn's `lax.psum(..., axis)` back into a psum, so
        # every cotangent strictly upstream of such a psum arrives
        # multiplied by the axis size, and cotangents on residual paths
        # are per-rank partials whose rank-sum is size x the true
        # cotangent (verified empirically vs jax.grad; see
        # tests/test_pipeline.py gradient-parity tests).  Hence, per
        # axis:
        #   - leaves SHARDED on the axis (spec mentions it) sit
        #     upstream of their block's psum: local shard gradient is
        #     exact x size -> divide by size;
        #   - leaves REPLICATED on the axis carry per-rank values whose
        #     sum over the axis is size x the true gradient -> pmean.
        for axis, size in ((tp_axis, tp), (ep_axis, ep)):
            if size <= 1:
                continue
            inv = 1.0 / size
            d_sh = jax.lax.pmean(d_sh, axis)
            d_st = jax.tree_util.tree_map(
                lambda g, spec, a=axis, iv=inv: g * iv
                if _spec_mentions(spec, a)
                else jax.lax.pmean(g, a),
                d_st, stage_specs)
        ok = None
        if with_finite:
            # grad health AFTER all reductions: a NaN/inf anywhere in
            # any rank's shard poisons its local sum of squares; psum
            # over every mesh axis makes the verdict identical on all
            # ranks (so `ok` can be returned replicated)
            leaves = (jax.tree_util.tree_leaves(d_sh)
                      + jax.tree_util.tree_leaves(d_st))
            g2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in leaves) if leaves else jnp.zeros(())
            bad = (nbad
                   + (~jnp.isfinite(g2)).astype(jnp.int32)
                   + (~jnp.isfinite(loss)).astype(jnp.int32))
            bad = jax.lax.psum(bad, pp_axis)
            for axis, size in ((dp_axis, dp), (tp_axis, tp),
                               (ep_axis, ep)):
                if size > 1:
                    bad = jax.lax.psum(bad, axis)
            ok = bad == 0
        # re-attach the local pp dim for the out_spec gather
        d_st = jax.tree_util.tree_map(lambda g: g[None], d_st)
        if with_finite:
            return loss, d_sh, d_st, ok
        return loss, d_sh, d_st

    repl = P()
    shared_specs = jax.tree_util.tree_map(lambda _: repl, shared)
    mb_spec = P(None, dp_axis)
    out_stage_specs = stage_specs
    out_specs = (repl, shared_specs, out_stage_specs)
    if with_finite:
        out_specs = out_specs + (repl,)
    from jax import shard_map
    out = shard_map(
        worker, mesh=mesh,
        in_specs=(shared_specs, stage_specs, mb_spec, mb_spec),
        out_specs=out_specs,
        check_vma=False)(shared, stages, ids_mb, labels_mb)
    if with_finite:
        loss, d_sh, d_st, ok = out
        return loss, (d_sh, d_st), ok
    loss, d_sh, d_st = out
    return loss, (d_sh, d_st)
