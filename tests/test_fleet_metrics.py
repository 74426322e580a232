"""fleet.metrics: distributed metric aggregation.

Reference analogue:
/root/reference/python/paddle/distributed/fleet/metrics/metric.py and
its unittest (test_fleet_metric.py): local accumulators allreduce to
the global metric.  Here the "trainers" are dp shards on the 8-device
CPU mesh; the in-trace route must psum over the mesh and match the
host-side single-process computation exactly.
"""
import numpy as np
import pytest  # noqa: F401
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from jax import shard_map
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import metrics as FM
from paddle_tpu.metric import Auc


class TestHostRoute:
    def test_sum_max_min_identity_single_process(self):
        x = np.array([1.0, 2.0, 3.0], 'float32')
        np.testing.assert_allclose(FM.sum(x), x)
        np.testing.assert_allclose(FM.max(x), x)
        np.testing.assert_allclose(FM.min(x), x)

    def test_tensor_input(self):
        t = paddle.to_tensor(np.array([2.0, 4.0], 'float32'))
        np.testing.assert_allclose(np.asarray(FM.sum(t)), [2.0, 4.0])

    def test_mae_mse_rmse_acc(self):
        assert FM.mae(np.array([6.0]), np.array([3.0])) == 2.0
        assert FM.mse(np.array([12.0]), np.array([3.0])) == 4.0
        assert FM.rmse(np.array([12.0]), np.array([3.0])) == 2.0
        assert FM.acc(np.array([9.0]), np.array([12.0])) == 0.75

    def test_auc_matches_metric_auc(self):
        rs = np.random.RandomState(0)
        scores = rs.rand(512).astype('float32')
        labels = (rs.rand(512) > 0.5).astype('int64')
        m = Auc(num_thresholds=255)
        m.update(scores[:, None], labels[:, None])
        got = FM.auc(m._stat_pos, m._stat_neg)
        want = m.accumulate()
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_auc_degenerate(self):
        z = np.zeros(16)
        assert FM.auc(z, z) == 0.5


class TestMeshRoute:
    def test_in_trace_psum_over_dp(self):
        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ('dp',))

        def step(x):
            local = jnp.sum(x)
            return (FM.sum(local), FM.max(local), FM.min(local))

        f = jax.jit(shard_map(step, mesh=mesh, in_specs=P('dp'),
                                  out_specs=(P(), P(), P())))
        x = np.arange(8, dtype='float32')
        s, mx, mn = f(x)
        assert float(s) == 28.0
        assert float(mx) == 7.0
        assert float(mn) == 0.0

    def test_dp_sharded_eval_auc_matches_single_process(self):
        """The VERDICT gate: a dp-sharded eval's bucket stats, psum'd
        over the mesh inside the compiled step, give the SAME global
        AUC as one process seeing the whole eval set."""
        rs = np.random.RandomState(7)
        n, buckets = 1024, 64
        scores = rs.rand(n).astype('float32')
        labels = (rs.rand(n) > 0.4).astype('float32')

        # single-process reference over the whole set
        ref = Auc(num_thresholds=buckets - 1)
        ref.update(scores[:, None], labels[:, None].astype('int64'))
        want = FM.auc(ref._stat_pos, ref._stat_neg)

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ('dp',))

        def eval_step(sc, lb):
            # jnp bucket histogram per shard (jit-safe), then the
            # in-trace fleet.metrics.sum over dp
            b = jnp.clip((sc * (buckets - 1)).astype(jnp.int32),
                         0, buckets - 1)
            pos = jnp.zeros(buckets).at[b].add(lb)
            neg = jnp.zeros(buckets).at[b].add(1.0 - lb)
            return FM.sum(pos), FM.sum(neg)

        f = jax.jit(shard_map(
            eval_step, mesh=mesh, in_specs=(P('dp'), P('dp')),
            out_specs=(P(), P())))
        gpos, gneg = f(scores, labels)
        got = FM.auc(np.asarray(gpos), np.asarray(gneg))
        np.testing.assert_allclose(got, want, rtol=1e-9)


class TestApiSurface:
    def test_fleet_namespace(self):
        for name in ('sum', 'max', 'min', 'auc', 'mae', 'rmse', 'mse',
                     'acc'):
            assert hasattr(fleet.metrics, name), name

    def test_custom_util(self):
        class FakeUtil:
            def all_reduce(self, arr, mode):
                return np.asarray(arr) * 2  # pretend 2 trainers

        out = FM.sum(np.array([3.0]), util=FakeUtil())
        np.testing.assert_allclose(out, [6.0])
