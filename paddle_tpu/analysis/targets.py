"""Built-in audit/planning targets: the lower-and-audit step factored
out of ``tools/tpu_lint.py`` so the ``--hlo`` SPMD audit and the
``--plan`` auto-sharding planner build the SAME step functions with
the SAME sharding resolution — and therefore can share one lowering
per (target, mesh) pair through ``hlo.lower_text``'s cache instead of
paying trace+lower twice when both run.

A *target* is ``builder(mesh) -> (model, example_batch)`` where
``example_batch`` is a tuple of ``jax.ShapeDtypeStruct`` placeholders
(shapes only — nothing here ever touches a device).  The suite
proxies what examples/ + paddle_tpu/models/ actually train: a tiny
GPT in the dp(+tp) posture, the WideDeep sparse-gather model, and the
LeNet vision path.
"""

__all__ = ['TARGETS', 'surrogate_step', 'target_state',
           'batch_shardings', 'cache_key']


def surrogate_step(model, remat=False):
    """forward + scalar surrogate loss + grad wrt params: the comms /
    sharding / liveness story of a train step without dragging a real
    optimizer into the audit.  ``remat=True`` wraps the forward in
    ``jax.checkpoint`` — the planner's remat fallback lowers THIS to
    price what strategy.recompute would buy."""
    import jax
    import jax.numpy as jnp
    from ..jit import functional_call

    def step(params, buffers, key, *batch):
        def loss_fn(p):
            def run(p):
                out, _ = functional_call(model, p, buffers, batch,
                                         key=key, training=True)
                return out
            if remat:
                run = jax.checkpoint(run)
            out = run(p)
            return sum(jnp.square(l.astype(jnp.float32)).mean()
                       for l in jax.tree_util.tree_leaves(out))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, grads

    return step


def target_state(model, mesh, param_specs=None):
    """(params, buffers) as ShapeDtypeStructs + their shardings.

    ``param_specs`` overrides the model's declared per-param specs
    (``collect_param_shardings``) — the planner passes each candidate
    assignment through here; the default resolution is the same one
    ParallelTrainer does."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.api import collect_param_shardings, make_spec
    params, buffers = model.functional_state()
    specs = param_specs if param_specs is not None \
        else collect_param_shardings(model)
    p_sh = {n: NamedSharding(mesh, make_spec(specs.get(n), v.ndim, mesh))
            for n, v in params.items()}
    repl = NamedSharding(mesh, P())
    b_sh = {n: repl for n in buffers}
    sds = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
    return ({n: sds(v) for n, v in params.items()},
            {n: sds(v) for n, v in buffers.items()}, p_sh, b_sh)


def batch_shardings(mesh, batch, axis=None):
    """Shard dim 0 of each batch placeholder over `axis` (default: the
    mesh's first >1 axis) when divisible; replicate otherwise."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if axis is None:
        axis = next((a for a in mesh.axis_names if mesh.shape[a] > 1),
                    None)
    repl = NamedSharding(mesh, P())
    return tuple(
        NamedSharding(mesh, P(axis))
        if axis is not None and b.shape
        and b.shape[0] % mesh.shape[axis] == 0
        else repl
        for b in batch)


def cache_key(target, mesh_axes, param_shardings, batch_shardings,
              remat=False, batch=()):
    """The shared lowering-memo key for one fully-resolved
    (target, mesh, shardings) triple.

    Keyed on the RESOLVED PartitionSpecs, not the assignment name:
    the planner's ``replicated`` candidate on a dp-only mesh resolves
    to the same program as the ``--hlo`` audit's declared-spec
    lowering there, and must hit the same memo entry.  Size-1 axes
    are elided so ``--mesh dp=8`` and the planner's
    ``{'dp': 8, 'tp': 1}`` candidate hash identically."""
    axes = tuple((a, int(s)) for a, s in dict(mesh_axes).items()
                 if int(s) > 1)

    def spec_of(sh):
        spec = getattr(sh, 'spec', sh)
        return str(tuple(spec)) if spec is not None else '()'

    pf = tuple(sorted((n, spec_of(s))
                      for n, s in dict(param_shardings).items()))
    bf = tuple(spec_of(s) for s in batch_shardings)
    shapes = tuple((tuple(b.shape), str(b.dtype)) for b in batch)
    return (str(target), axes, pf, bf, bool(remat), shapes)


def _ids_batch(shape, vocab):
    import jax
    import jax.numpy as jnp
    del vocab     # shapes only: lowering never reads values
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _target_gpt(mesh):
    """Tiny GPT in the dp(+tp) posture of examples/gpt_train_generate
    and examples/distributed_hybrid."""
    import paddle_tpu as paddle
    from ..models.gpt import GPT, GPTConfig
    del mesh
    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                          num_heads=4, max_seq_len=32, dropout=0.0))
    return model, (_ids_batch((8, 16), 128),)


def _target_widedeep(mesh):
    """WideDeep sparse-gather model (paddle_tpu/models/widedeep)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from ..models.widedeep import WideDeep
    del mesh
    paddle.seed(0)
    model = WideDeep([16, 16, 16, 16], dense_dim=4, embed_dim=8,
                     shard_vocab=False)
    return model, (_ids_batch((8, 4), 16),
                   jax.ShapeDtypeStruct((8, 4), jnp.float32))


def _target_gptserve(mesh):
    """One paged decode step of the serving engine
    (serving/engine.DecodeAuditLayer): a ragged live batch attending
    the paged KV pool through per-sequence block tables — the
    continuous-batching serving surface, auditable/plannable like any
    train step."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from ..models.gpt import GPTConfig, GPTForCausalLM
    from ..serving.engine import DecodeAuditLayer
    del mesh
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=32, dropout=0.0))
    model.eval()
    L, nh, hd = 2, 4, 16
    S, bs, mb = 8, 8, 4                   # batch, block size, table w
    nb = S * mb + 1                       # pool incl. trash block
    return DecodeAuditLayer(model), (
        _ids_batch((S, 1), 128),
        jax.ShapeDtypeStruct((L, nb, bs, nh, hd), jnp.float32),
        jax.ShapeDtypeStruct((L, nb, bs, nh, hd), jnp.float32),
        _ids_batch((S, mb), 0),
        _ids_batch((S,), 0))


def _target_lenet(mesh):
    """LeNet vision path of examples/mnist_lenet."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from ..vision.models import LeNet
    del mesh
    paddle.seed(0)
    model = LeNet()
    return model, (jax.ShapeDtypeStruct((8, 1, 28, 28), jnp.float32),)


# target name -> builder(mesh) -> (model, example_batch); the suite
# proxies what examples/ + paddle_tpu/models/ actually train
TARGETS = {
    'gpt': _target_gpt,
    'widedeep': _target_widedeep,
    'lenet': _target_lenet,
    'gptserve': _target_gptserve,
}
