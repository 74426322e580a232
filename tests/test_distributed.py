"""Distributed tests on the 8-device virtual CPU mesh.

Mirrors reference tests:
/root/reference/python/paddle/fluid/tests/unittests/test_collective_*,
test_parallel_dygraph_*, fleet tests — but in-process: XLA virtual
devices replace multi-process NCCL workers.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from jax import shard_map
import paddle_tpu.nn as nn
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet, collective, env as dist_env
from paddle_tpu.parallel import ParallelTrainer


@pytest.fixture(autouse=True)
def clean_mesh():
    yield
    dist_env.set_mesh(None)


def test_eight_devices():
    assert jax.device_count() == 8


class TestCollectives:
    def test_all_reduce_inside_shard_map(self):
        mesh = dist.build_mesh({'dp': 8})
        dist.set_mesh(mesh)

        def body(x):
            with collective.axis_scope('dp'):
                t = paddle.to_tensor(x)
                out = dist.all_reduce(t)
            return out.value

        xs = jnp.arange(8.0)
        y = shard_map(body, mesh=mesh, in_specs=P('dp'),
                          out_specs=P('dp'))(xs)
        np.testing.assert_allclose(np.asarray(y), np.full(8, 28.0))

    def test_all_reduce_identity_outside(self):
        t = paddle.to_tensor([1.0, 2.0])
        out = dist.all_reduce(t)
        np.testing.assert_allclose(np.asarray(out.value), [1.0, 2.0])

    def test_broadcast(self):
        mesh = dist.build_mesh({'dp': 8})
        dist.set_mesh(mesh)

        def body(x):
            with collective.axis_scope('dp'):
                out = dist.broadcast(paddle.to_tensor(x), src=3)
            return out.value

        xs = jnp.arange(8.0)
        y = shard_map(body, mesh=mesh, in_specs=P('dp'),
                          out_specs=P('dp'))(xs)
        np.testing.assert_allclose(np.asarray(y), np.full(8, 3.0))

    def test_all_gather(self):
        mesh = dist.build_mesh({'dp': 8})
        dist.set_mesh(mesh)

        def body(x):
            with collective.axis_scope('dp'):
                got = dist.all_gather([], paddle.to_tensor(x))
            return got.value

        xs = jnp.arange(8.0).reshape(8, 1)
        y = shard_map(body, mesh=mesh, in_specs=P('dp'),
                          out_specs=P(None, 'dp'))(xs)
        assert np.asarray(y).shape == (8, 8)

    def test_p2p_rotate(self):
        mesh = dist.build_mesh({'pp': 8})
        dist.set_mesh(mesh)

        def body(x):
            with collective.axis_scope('pp'):
                out = collective.p2p_rotate(paddle.to_tensor(x), shift=1)
            return out.value

        xs = jnp.arange(8.0)
        y = shard_map(body, mesh=mesh, in_specs=P('pp'),
                          out_specs=P('pp'))(xs)
        np.testing.assert_allclose(np.asarray(y),
                                   np.roll(np.arange(8.0), 1))


class TestFleetInit:
    def test_hybrid_mesh(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs['dp_degree'] = 2
        strategy.hybrid_configs['mp_degree'] = 2
        strategy.hybrid_configs['pp_degree'] = 2
        fleet.init(is_collective=True, strategy=strategy)
        mesh = dist.get_mesh()
        assert dict(mesh.shape) == {'pp': 2, 'dp': 2, 'sp': 1,
                                    'ep': 1, 'tp': 2}
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_data_parallel_world_size() == 2

    def test_infer_dp_degree(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs['mp_degree'] = 4
        fleet.init(strategy=strategy)
        assert dict(dist.get_mesh().shape)['dp'] == 2


class TestTensorParallel:
    def _mlp_data(self):
        rs = np.random.RandomState(0)
        return rs.randn(4, 16).astype('float32')

    def test_tp_mlp_matches_plain(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs['mp_degree'] = 4
        strategy.hybrid_configs['dp_degree'] = 2
        fleet.init(strategy=strategy)

        paddle.seed(0)
        col = fleet.ColumnParallelLinear(16, 32, gather_output=False)
        row = fleet.RowParallelLinear(32, 16, input_is_parallel=True)
        x = paddle.to_tensor(self._mlp_data())

        # eager single-logical-device forward (mesh present but not traced)
        y_eager = np.asarray(row(col(x)).value)

        # plain layers with identical weights
        lin1, lin2 = nn.Linear(16, 32), nn.Linear(32, 16)
        lin1.weight.set_value(col.weight.value)
        lin1.bias.set_value(col.bias.value)
        lin2.weight.set_value(row.weight.value)
        lin2.bias.set_value(row.bias.value)
        y_plain = np.asarray(lin2(lin1(x)).value)
        np.testing.assert_allclose(y_eager, y_plain, rtol=1e-5, atol=1e-5)

        # compiled SPMD forward over the mesh must match too
        from paddle_tpu.jit import functional_call
        mesh = dist.get_mesh()
        net = nn.Sequential(col, row)
        params, buffers = net.functional_state()
        from paddle_tpu.parallel.api import collect_param_shardings, \
            named_sharding
        specs = collect_param_shardings(net)
        params = {n: jax.device_put(v, named_sharding(specs[n], v.ndim))
                  for n, v in params.items()}

        @jax.jit
        def fwd(params, xv):
            out, _ = functional_call(net, params, buffers, (xv,),
                                     training=False)
            return out
        y_spmd = np.asarray(fwd(params, x.value))
        np.testing.assert_allclose(y_spmd, y_plain, rtol=1e-4, atol=1e-4)

    def test_vocab_parallel_embedding(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs['mp_degree'] = 8
        fleet.init(strategy=strategy)
        emb = fleet.VocabParallelEmbedding(64, 16)
        ids = paddle.to_tensor(np.array([[1, 5, 63], [0, 2, 7]]))
        out = emb(ids)
        assert out.shape == [2, 3, 16]


class TestParallelTrainer:
    def _make(self, strategy=None, lr=0.1):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        opt = paddle.optimizer.Momentum(learning_rate=lr,
                                        parameters=net.parameters())
        loss_fn = lambda out, y: ((out - y) ** 2).mean()
        return net, opt, loss_fn

    def _data(self):
        rs = np.random.RandomState(1)
        X = rs.randn(16, 8).astype('float32')
        Y = (X.sum(1, keepdims=True) > 0).astype('float32')
        return X, Y

    def test_dp_training_decreases_loss(self):
        dist.init_parallel_env(axes={'dp': 8})
        net, opt, loss_fn = self._make()
        trainer = ParallelTrainer(net, opt, loss_fn)
        X, Y = self._data()
        losses = [float(np.asarray(trainer.step(X, Y))) for _ in range(30)]
        assert losses[-1] < losses[0] * 0.3, losses[:3] + losses[-3:]

    def test_dp_matches_single_device(self):
        X, Y = self._data()

        dist.init_parallel_env(axes={'dp': 8})
        net, opt, loss_fn = self._make()
        tr_dp = ParallelTrainer(net, opt, loss_fn)
        l_dp = [float(np.asarray(tr_dp.step(X, Y))) for _ in range(5)]

        dist_env.set_mesh(None)
        dist.init_parallel_env(axes={'dp': 1})
        # rebuild identical net (same seed)
        net1, opt1, loss_fn = self._make()
        tr_1 = ParallelTrainer(net1, opt1, loss_fn)
        l_1 = [float(np.asarray(tr_1.step(X, Y))) for _ in range(5)]
        np.testing.assert_allclose(l_dp, l_1, rtol=1e-4, atol=1e-5)

    def test_zero_shards_optimizer_state(self):
        dist.init_parallel_env(axes={'dp': 8})
        strategy = fleet.DistributedStrategy()
        strategy.sharding = True
        paddle.seed(0)
        net = nn.Linear(8, 64)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        loss_fn = lambda out, y: ((out - y) ** 2).mean()
        tr = ParallelTrainer(net, opt, loss_fn, strategy=strategy)
        # Adam moment for the weight should be sharded over dp on dim 0
        m = tr.opt_state['weight']['moment1']
        sh = m.sharding
        assert isinstance(sh, NamedSharding)
        assert sh.spec == P('dp'), sh.spec
        X = np.random.RandomState(0).randn(16, 8).astype('float32')
        Y = np.zeros((16, 64), 'float32')
        l0 = float(np.asarray(tr.step(X, Y)))
        l5 = l0
        for _ in range(10):
            l5 = float(np.asarray(tr.step(X, Y)))
        assert l5 < l0

    def test_gradient_merge(self):
        dist.init_parallel_env(axes={'dp': 1})
        strategy = fleet.DistributedStrategy()
        strategy.gradient_merge = True
        strategy.gradient_merge_configs['k_steps'] = 4
        net, opt, loss_fn = self._make()
        tr = ParallelTrainer(net, opt, loss_fn, strategy=strategy)
        X, Y = self._data()
        l0 = float(np.asarray(tr.step(X, Y)))
        l1 = l0
        for _ in range(20):
            l1 = float(np.asarray(tr.step(X, Y)))
        assert l1 < l0

    def test_recompute_matches(self):
        X, Y = self._data()
        dist.init_parallel_env(axes={'dp': 1})
        strategy = fleet.DistributedStrategy()
        strategy.recompute = True
        net, opt, loss_fn = self._make()
        tr = ParallelTrainer(net, opt, loss_fn, strategy=strategy)
        l_r = [float(np.asarray(tr.step(X, Y))) for _ in range(5)]
        net2, opt2, loss_fn = self._make()
        tr2 = ParallelTrainer(net2, opt2, loss_fn)
        l_p = [float(np.asarray(tr2.step(X, Y))) for _ in range(5)]
        np.testing.assert_allclose(l_r, l_p, rtol=1e-5, atol=1e-6)


class TestDataParallelWrapper:
    def test_transparent_single_chip(self):
        net = nn.Linear(4, 2)
        dp = dist.DataParallel(net)
        x = paddle.ones([3, 4])
        np.testing.assert_allclose(np.asarray(dp(x).value),
                                   np.asarray(net(x).value))
        loss = dp(x).mean()
        loss = dp.scale_loss(loss)
        loss.backward()
        dp.apply_collective_grads()
        assert net.weight.grad is not None


class TestRingAttention:
    """SURVEY.md §2 item 35: sequence parallelism via ppermute KV ring."""

    def _losses(self, axes, sequence_parallel, n_steps=4):
        return self._losses_cfg(axes, n_steps=n_steps,
                                sequence_parallel=sequence_parallel)

    def test_ring_matches_single_device(self):
        l_sp = self._losses({'sp': 8}, True)
        l_1 = self._losses({'sp': 1}, False)
        np.testing.assert_allclose(l_sp, l_1, rtol=2e-4, atol=2e-4)

    def _losses_cfg(self, axes, n_steps=3, fused_head=False, **cfg):
        dist_env.set_mesh(None)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs['dp_degree'] = 1
        for k, v in axes.items():
            key = {'dp': 'dp_degree', 'tp': 'mp_degree',
                   'sp': 'sp_degree'}[k]
            strategy.hybrid_configs[key] = v
        fleet.init(strategy=strategy)
        paddle.seed(0)
        from paddle_tpu.models import gpt_tiny
        m = gpt_tiny(num_layers=2, hidden_size=32, num_heads=2,
                     dropout=0.0, fused_head=fused_head, **cfg)
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        tr = ParallelTrainer(m, opt, lambda out, y: m.loss(out, y))
        ids = np.random.RandomState(0).randint(0, 128, (4, 16)) \
            .astype('int64')
        return [float(np.asarray(tr.step(ids, ids)))
                for _ in range(n_steps)]

    def test_striped_sp_matches_natural(self):
        # end-to-end striped layout (ids/positions striped at the
        # embedding, shift-then-stripe labels in the fused CE): the
        # per-token mean is permutation-invariant, so losses match
        l_striped = self._losses_cfg({'sp': 4}, fused_head=True,
                                     sequence_parallel=True,
                                     striped_sp=True)
        l_natural = self._losses_cfg({'sp': 4}, fused_head=True,
                                     sequence_parallel=True)
        l_single = self._losses_cfg({'sp': 1}, fused_head=True)
        np.testing.assert_allclose(l_striped, l_natural,
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(l_striped, l_single,
                                   rtol=2e-4, atol=2e-4)

    def test_ring_hybrid_mesh(self):
        l_h = self._losses({'dp': 2, 'tp': 2, 'sp': 2}, True)
        l_1 = self._losses({'sp': 1}, False)
        np.testing.assert_allclose(l_h, l_1, rtol=2e-4, atol=2e-4)

    def test_ring_op_direct(self):
        from paddle_tpu.ops.ring_attention import ring_attention_spmd
        from paddle_tpu.ops.flash_attention import _reference
        from jax.sharding import Mesh
        import math
        rs = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rs.randn(2, 64, 16), jnp.float32)
                   for _ in range(3))
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ('sp',))
        out = jax.jit(lambda q, k, v: ring_attention_spmd(
            q, k, v, mesh, causal=True, batch_axes=()))(q, k, v)
        ref = _reference(q, k, v, True, 1.0 / math.sqrt(16))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestGPipe:
    """SURVEY.md §2 item 29: GPipe microbatch rotation over pp axis."""

    def _setup(self):
        from jax.sharding import Mesh
        rs = np.random.RandomState(0)
        S, H = 4, 16
        params = {'w': jnp.asarray(rs.randn(S, H, H) * 0.3, jnp.float32),
                  'b': jnp.asarray(rs.randn(S, H) * 0.1, jnp.float32)}

        def stage(p, x):
            return jax.nn.relu(x @ p['w'] + p['b'])

        x = jnp.asarray(rs.randn(16, H), jnp.float32)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ('pp',))
        return params, stage, x, mesh, S

    def _seq_ref(self, params, stage, x, S):
        y = x
        for s in range(S):
            y = stage(jax.tree_util.tree_map(lambda p: p[s], params), y)
        return y

    def test_forward_matches_sequential(self):
        from paddle_tpu.parallel.pipeline import gpipe_spmd
        params, stage, x, mesh, S = self._setup()
        out = jax.jit(lambda p, x: gpipe_spmd(
            p, x, stage, mesh, num_microbatches=4))(params, x)
        ref = self._seq_ref(params, stage, x, S)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_match_sequential(self):
        from paddle_tpu.parallel.pipeline import gpipe_spmd
        params, stage, x, mesh, S = self._setup()
        gp = jax.jit(jax.grad(lambda p: (gpipe_spmd(
            p, x, stage, mesh, 4) ** 2).sum()))(params)
        gr = jax.grad(lambda p: (self._seq_ref(
            params | p, stage, x, S) ** 2).sum())(params)
        for k in gp:
            np.testing.assert_allclose(np.asarray(gp[k]),
                                       np.asarray(gr[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_microbatch_counts(self):
        from paddle_tpu.parallel.pipeline import gpipe_spmd
        params, stage, x, mesh, S = self._setup()
        ref = self._seq_ref(params, stage, x, S)
        for m in (1, 2, 8, 16):
            out = jax.jit(lambda p, x: gpipe_spmd(
                p, x, stage, mesh, num_microbatches=m))(params, x)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)


class TestLaunchModule:
    def test_single_host_launch_runs_script(self, tmp_path):
        """python -m paddle_tpu.distributed.launch runs the script with
        sys.argv rewritten; single host skips jax.distributed init."""
        import subprocess, sys, os
        script = tmp_path / 'train.py'
        script.write_text(
            'import sys\n'
            'import paddle_tpu as paddle\n'
            "print('RANK', paddle.distributed.get_rank(), sys.argv[1])\n")
        env = dict(os.environ)
        env['JAX_PLATFORMS'] = 'cpu'
        env['PYTHONPATH'] = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep + \
            env.get('PYTHONPATH', '')
        out = subprocess.run(
            [sys.executable, '-m', 'paddle_tpu.distributed.launch',
             str(script), '--flag'],
            capture_output=True, text=True, timeout=300, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        assert 'RANK 0 --flag' in out.stdout


class TestFleetSurface:
    """Fleet namespace parity: topology, role makers, util, data
    generators, fleet.utils (reference fleet/base/*, fleet/utils/*)."""

    def test_communicate_topology(self):
        from paddle_tpu.distributed.fleet import CommunicateTopology
        topo = CommunicateTopology(['data', 'model'], [2, 3])
        assert topo.world_size() == 6
        assert topo.get_dim('model') == 3
        r = topo.get_rank(data=1, model=2)
        assert topo.get_coord(r) == (1, 2)
        assert topo.get_axis_list('data', 0) == [0, 1, 2]
        comm = topo.get_comm_list('model')
        assert [0, 1, 2] in comm and [3, 4, 5] in comm

    def test_topology_from_mesh(self):
        from paddle_tpu.distributed.fleet import (CommunicateTopology,
                                                  DistributedStrategy)
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed import env as dist_env
        s = DistributedStrategy()
        s.hybrid_configs['dp_degree'] = 2
        s.hybrid_configs['mp_degree'] = 2
        fleet.init(is_collective=True, strategy=s)
        try:
            mesh = dist_env.get_mesh()
            topo = CommunicateTopology.from_mesh(mesh)
            assert topo.world_size() == mesh.devices.size
            assert topo.get_dim('dp') == 2 and topo.get_dim('tp') == 2
        finally:
            dist_env.set_mesh(None)

    def test_role_makers(self):
        from paddle_tpu.distributed.fleet import (PaddleCloudRoleMaker,
                                                  UserDefinedRoleMaker,
                                                  Role)
        rm = PaddleCloudRoleMaker(is_collective=True)
        assert rm._is_worker() and rm._is_first_worker()
        u = UserDefinedRoleMaker(current_id=2, worker_num=4,
                                 role=Role.WORKER,
                                 worker_endpoints=['a:1', 'b:2'])
        assert u._worker_index() == 2 and u._worker_num() == 4
        assert u._get_trainer_endpoints() == ['a:1', 'b:2']

    def test_util_file_shard_and_allreduce(self):
        from paddle_tpu.distributed import fleet
        files = [f'f{i}' for i in range(5)]
        assert fleet.util.get_file_shard(files) == files  # 1 process
        out = fleet.util.all_reduce(np.asarray([1.0, 2.0]), mode='sum')
        np.testing.assert_allclose(out, [1.0, 2.0])
        fleet.util.barrier()

    def test_multislot_data_generators(self):
        from paddle_tpu.distributed.fleet import (
            MultiSlotDataGenerator, MultiSlotStringDataGenerator)

        class G(MultiSlotDataGenerator):
            def generate_sample(self, line):
                def gen():
                    a, b = line.split(',')
                    yield [('label', [int(a)]), ('feat', [float(b), 1.0])]
                return gen
        out = G().run_from_memory(['1,0.5', '0,2.5'])
        assert out == ['1 1 2 0.5 1.0', '1 0 2 2.5 1.0']

        class S(MultiSlotStringDataGenerator):
            def generate_sample(self, line):
                def gen():
                    yield [('words', line.split())]
                return gen
        assert S().run_from_memory(['a b c']) == ['a b c']

    def test_local_fs(self, tmp_path):
        from paddle_tpu.distributed.fleet.utils import LocalFS
        fs = LocalFS()
        d = str(tmp_path / 'x')
        fs.mkdirs(d)
        assert fs.is_dir(d) and fs.is_exist(d)
        f = str(tmp_path / 'x' / 'a.txt')
        fs.touch(f)
        assert fs.is_file(f)
        dirs, files = fs.ls_dir(str(tmp_path / 'x'))
        assert files == ['a.txt']
        fs.mv(f, str(tmp_path / 'b.txt'))
        assert fs.is_file(str(tmp_path / 'b.txt'))
        fs.delete(d)
        assert not fs.is_exist(d)

    def test_hdfs_requires_hadoop(self):
        from paddle_tpu.distributed.fleet.utils import HDFSClient
        import shutil as _sh
        if _sh.which('hadoop'):
            pytest.skip('hadoop actually present')
        with pytest.raises(RuntimeError, match='hadoop'):
            HDFSClient()

    def test_recompute_matches_plain(self):
        from paddle_tpu.distributed.fleet.utils import recompute
        x = paddle.to_tensor(np.linspace(-1, 1, 8).astype('float32'))
        x.stop_gradient = False

        def block(t):
            return paddle.tanh(t) * t
        y = recompute(block, x).sum()
        y.backward()
        g_re = x.grad.numpy().copy()
        x2 = paddle.to_tensor(np.linspace(-1, 1, 8).astype('float32'))
        x2.stop_gradient = False
        block(x2).sum().backward()
        np.testing.assert_allclose(g_re, x2.grad.numpy(), rtol=1e-5)


class TestSplitLayerCache:
    """The eager name-keyed split() cache must not survive a fleet
    re-init with a different topology (advisor r3: stale per-shard
    weight shapes, cross-test weight leaks)."""

    def test_reinit_new_topology_clears_cache(self):
        from paddle_tpu.distributed import mp_ops
        import paddle_tpu.distributed as dist
        strategy = fleet.DistributedStrategy()
        fleet.init(is_collective=True, strategy=strategy)
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype('float32'))
        dist.split(x, (8, 4), 'linear', axis=1, name='cache_probe')
        assert any(k[0] == 'cache_probe' for k in mp_ops._LAYER_CACHE)
        # same topology re-init: jax interns the Mesh, cache survives
        # (name-keyed reuse is the documented feature)
        fleet.init(is_collective=True,
                   strategy=fleet.DistributedStrategy())
        assert any(k[0] == 'cache_probe' for k in mp_ops._LAYER_CACHE)
        # switching topology keeps the outgoing mesh's entries (a
        # program alternating train/aux meshes must not lose trained
        # weights) but a SECOND switch away evicts them
        s2 = fleet.DistributedStrategy()
        s2.hybrid_configs = {'mp_degree': 2}
        fleet.init(is_collective=True, strategy=s2)
        assert any(k[0] == 'cache_probe' for k in mp_ops._LAYER_CACHE)
        s3 = fleet.DistributedStrategy()
        s3.hybrid_configs = {'mp_degree': 4}
        fleet.init(is_collective=True, strategy=s3)
        assert not any(k[0] == 'cache_probe'
                       for k in mp_ops._LAYER_CACHE)

    def test_cache_key_includes_mesh(self):
        from paddle_tpu.distributed import mp_ops
        import paddle_tpu.distributed as dist
        fleet.init(is_collective=True,
                   strategy=fleet.DistributedStrategy())
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype('float32'))
        dist.split(x, (8, 4), 'linear', axis=1, name='mesh_probe')
        key = next(k for k in mp_ops._LAYER_CACHE
                   if k[0] == 'mesh_probe')
        assert dist_env.get_mesh() in key

    def test_set_mesh_bounds_cache(self):
        from paddle_tpu.distributed import mp_ops
        import paddle_tpu.distributed as dist
        # isolate the direct-switch policy from meshes other tests
        # parked in the None-gap recent window
        dist_env._recent_real = []
        fleet.init(is_collective=True,
                   strategy=fleet.DistributedStrategy())
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype('float32'))
        dist.split(x, (8, 4), 'linear', axis=1, name='evict_probe')
        assert mp_ops._LAYER_CACHE
        mesh = dist_env.get_mesh()
        dist_env.set_mesh(mesh)          # same mesh: cache survives
        assert mp_ops._LAYER_CACHE
        # A → B: outgoing mesh's entries survive (weights preserved
        # for a program that returns to A) …
        mesh_b = Mesh(np.array(jax.devices()).reshape(4, 2),
                      ('dp', 'tp'))
        dist_env.set_mesh(mesh_b)
        assert any(k[0] == 'evict_probe' for k in mp_ops._LAYER_CACHE)
        # … but B → C evicts A's entries: growth is bounded to the
        # current + previous meshes
        mesh_c = Mesh(np.array(jax.devices()).reshape(2, 4),
                      ('dp', 'tp'))
        dist_env.set_mesh(mesh_c)
        assert not any(k[0] == 'evict_probe'
                       for k in mp_ops._LAYER_CACHE)
        dist_env.set_mesh(mesh)

    def test_none_bridge_preserves_train_mesh_entries(self):
        # A → None (teardown) → B must NOT evict A's trained layers
        from paddle_tpu.distributed import mp_ops
        import paddle_tpu.distributed as dist
        fleet.init(is_collective=True,
                   strategy=fleet.DistributedStrategy())
        mesh_a = dist_env.get_mesh()
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype('float32'))
        dist.split(x, (8, 4), 'linear', axis=1, name='bridge_probe')
        dist_env.set_mesh(None)
        mesh_b = Mesh(np.array(jax.devices()).reshape(4, 2),
                      ('dp', 'tp'))
        dist_env.set_mesh(mesh_b)
        assert any(k[0] == 'bridge_probe'
                   for k in mp_ops._LAYER_CACHE)
        # returning to A reuses the SAME trained layer
        dist_env.set_mesh(mesh_a)
        key = next(k for k in mp_ops._LAYER_CACHE
                   if k[0] == 'bridge_probe')
        layer = mp_ops._LAYER_CACHE[key]
        dist.split(x, (8, 4), 'linear', axis=1, name='bridge_probe')
        assert mp_ops._LAYER_CACHE[key] is layer
        dist_env.set_mesh(mesh_a)

    def test_double_none_gap_preserves_entries(self):
        # A → None → B → None → A must keep A's trained layers
        from paddle_tpu.distributed import mp_ops
        import paddle_tpu.distributed as dist
        fleet.init(is_collective=True,
                   strategy=fleet.DistributedStrategy())
        mesh_a = dist_env.get_mesh()
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype('float32'))
        dist.split(x, (8, 4), 'linear', axis=1, name='gap2_probe')
        key = next(k for k in mp_ops._LAYER_CACHE
                   if k[0] == 'gap2_probe')
        layer = mp_ops._LAYER_CACHE[key]
        dist_env.set_mesh(None)
        dist_env.set_mesh(Mesh(np.array(jax.devices()).reshape(4, 2),
                               ('dp', 'tp')))
        dist_env.set_mesh(None)
        dist_env.set_mesh(mesh_a)
        assert mp_ops._LAYER_CACHE.get(key) is layer
