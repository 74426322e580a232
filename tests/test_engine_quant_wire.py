"""Quantized wire (ISSUE 14): block-scaled int8 collectives.

Covers the pure quantize/dequant core (bit-stable round trip,
deterministic stochastic rounding), the shard_map all-reduce
decomposition (sum/mean parity, master accumulation, min-bytes
fallback), ParallelTrainer/LocalSGD integration (convergence next to
full width, s8 census evidence, sync-free transfer guard, degrade
warnings), the HostCollectives int8 frame (cluster-bitwise equality,
corrupt-after-crc rejection, restart replay), the packed-int4 PTQ
backend (pack/unpack losslessness + int8-path parity, serving swap),
the cost model's wire-dtype dimension, and the planner's
quantization recommendation.

File name sorts before test_host_embedding so tier-1 runs it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from jax import shard_map
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.parallel import (ParallelTrainer, LocalSGDTrainer,
                                 QuantCollectiveConfig,
                                 resolve_quant_collectives)
from paddle_tpu.parallel import quant_collectives as qc


@pytest.fixture
def mesh():
    prev = dist_env.get_mesh()
    m = dist_env.build_mesh({'dp': 8})
    dist_env.set_mesh(m)
    yield m
    dist_env.set_mesh(prev)


def _cfg(**kw):
    kw.setdefault('min_bytes', 0)
    return QuantCollectiveConfig(**kw)


# =============================================================================
# pure core
# =============================================================================

class TestQuantCore:
    def test_round_trip_bit_stable(self):
        x = jnp.asarray(np.random.RandomState(0).randn(2048),
                        jnp.float32)
        q, s = qc.quantize_blocks(x, 256)
        d = qc.dequantize_blocks(q, s)
        # grid values re-quantize to the identical payload under the
        # same scales — twice
        q2, _ = qc.quantize_blocks(d, 256, scales=s)
        q3, _ = qc.quantize_blocks(d, 256, scales=s)
        assert jnp.array_equal(q, q2)
        assert jnp.array_equal(q2, q3)
        assert jnp.array_equal(d, qc.dequantize_blocks(q2, s))

    def test_stochastic_same_key_same_draw(self):
        x = jnp.asarray(np.random.RandomState(1).randn(1024),
                        jnp.float32)
        k = jax.random.PRNGKey(7)
        qa, _ = qc.quantize_blocks(x, 256, key=k)
        qb, _ = qc.quantize_blocks(x, 256, key=k)
        assert jnp.array_equal(qa, qb)
        qc_, _ = qc.quantize_blocks(x, 256,
                                    key=jax.random.PRNGKey(8))
        assert not jnp.array_equal(qa, qc_)

    def test_quantization_error_bounded_by_block_absmax(self):
        x = jnp.asarray(np.random.RandomState(2).randn(4096),
                        jnp.float32)
        q, s = qc.quantize_blocks(x, 256, key=jax.random.PRNGKey(0))
        d = qc.dequantize_blocks(q, s).reshape(-1)
        err = jnp.abs(d - x).reshape(-1, 256)
        # stochastic rounding moves at most one grid cell: |e| <= scale
        assert bool(jnp.all(err <= s[:, None] * (1 + 1e-6)))

    def test_step_key_pure_in_step(self):
        cfg = _cfg()
        assert jnp.array_equal(qc.step_key(cfg, 5), qc.step_key(cfg, 5))
        assert not jnp.array_equal(qc.step_key(cfg, 5),
                                   qc.step_key(cfg, 6))

    def test_resolve_semantics(self, monkeypatch):
        assert resolve_quant_collectives(False) is None
        assert resolve_quant_collectives(None, env='') is None
        assert resolve_quant_collectives(None, env='0') is None
        got = resolve_quant_collectives(None, env='int8,block=128')
        assert got.block == 128 and got.dtype == 'int8'
        got = resolve_quant_collectives(
            'int8,master_accum=1,stochastic=0')
        assert got.master_accum and not got.stochastic
        assert resolve_quant_collectives('int8') == \
            QuantCollectiveConfig()
        assert resolve_quant_collectives(
            {'block': 64}).block == 64
        with pytest.raises(ValueError):
            QuantCollectiveConfig(dtype='int4')
        with pytest.raises(ValueError):
            resolve_quant_collectives(None, env='int8,bogus=1')

    def test_wire_factor(self):
        # int8 + one f32 scale per 256 elements over f32 ~ 0.254
        assert abs(qc.wire_factor(_cfg()) - (1 + 4 / 256) / 4) < 1e-9


# =============================================================================
# shard_map all-reduce decomposition
# =============================================================================

class TestQuantizedAllreduce:
    def _run(self, cfg, vals, op='mean', key_step=3):
        m = dist_env.build_mesh({'dp': 8})

        def body(v):
            k = qc.step_key(cfg, key_step) if cfg.stochastic else None
            return qc.quantized_allreduce(
                v[0], 'dp', n=8, cfg=cfg, key=k, op=op)[None]

        return np.asarray(jax.jit(shard_map(
            body, mesh=m, in_specs=P('dp'), out_specs=P('dp'),
            check_vma=False))(jnp.asarray(vals)))

    def test_mean_close_and_replicated(self):
        vals = np.random.RandomState(0).randn(8, 4096).astype('f4')
        out = self._run(_cfg(), vals)
        ref = vals.mean(0)
        for r in range(8):
            assert np.array_equal(out[0], out[r])
        assert np.abs(out[0] - ref).max() < 0.05 * vals.std()

    def test_sum_op(self):
        vals = np.random.RandomState(1).randn(8, 2048).astype('f4')
        out = self._run(_cfg(stochastic=False), vals, op='sum')
        ref = vals.sum(0)
        assert np.abs(out[0] - ref).max() < 0.1 * np.abs(ref).std()

    def test_master_accum_tighter(self):
        vals = np.random.RandomState(2).randn(8, 4096).astype('f4')
        ref = vals.mean(0)
        e_q = np.abs(self._run(_cfg(stochastic=False), vals)[0]
                     - ref).max()
        e_m = np.abs(self._run(
            _cfg(stochastic=False, master_accum=True), vals)[0]
            - ref).max()
        # the exact-sum escape hatch quantizes once, not twice
        assert e_m <= e_q

    def test_odd_sizes_pad_and_slice(self):
        vals = np.random.RandomState(3).randn(8, 999).astype('f4')
        out = self._run(_cfg(stochastic=False), vals)
        assert out.shape == (8, 999)
        assert np.abs(out[0] - vals.mean(0)).max() < 0.1

    def test_min_bytes_falls_back_full_width(self):
        cfg = QuantCollectiveConfig(min_bytes=1 << 30)
        m = dist_env.build_mesh({'dp': 8})

        def body(v):
            t = qc.quantized_allreduce_tree(
                {'w': v[0]}, 'dp', n=8, cfg=cfg, op='mean')
            return t['w'][None]

        f = jax.jit(shard_map(body, mesh=m, in_specs=P('dp'),
                              out_specs=P('dp'), check_vma=False))
        vals = np.random.RandomState(4).randn(8, 64).astype('f4')
        out = np.asarray(f(jnp.asarray(vals)))
        # full width: bitwise pmean, no int8 ops in the module
        assert np.allclose(out[0], vals.mean(0), rtol=1e-6)
        text = f.lower(jnp.asarray(vals)).compile().as_text()
        assert 'all-to-all' not in text
        assert 's8[' not in text

    def test_tree_round_trips_shapes_and_dtypes(self):
        cfg = _cfg(stochastic=False)
        m = dist_env.build_mesh({'dp': 8})
        tree = {'a': np.random.RandomState(5).randn(8, 3, 5)
                .astype('f4'),
                'b': np.random.RandomState(6).randn(8, 70)
                .astype('f4')}

        def body(a, b):
            t = qc.quantized_allreduce_tree(
                {'a': a[0], 'b': b[0]}, 'dp', n=8, cfg=cfg, op='mean')
            return t['a'][None], t['b'][None]

        a, b = jax.jit(shard_map(
            body, mesh=m, in_specs=(P('dp'), P('dp')),
            out_specs=(P('dp'), P('dp')), check_vma=False))(
            jnp.asarray(tree['a']), jnp.asarray(tree['b']))
        assert a.shape == (8, 3, 5) and b.shape == (8, 70)
        assert np.abs(np.asarray(a)[0]
                      - tree['a'].mean(0)).max() < 0.1


# =============================================================================
# ParallelTrainer integration
# =============================================================================

def _make_trainer(mesh, quant, **kw):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(32, 64), nn.ReLU(),
                        nn.Linear(64, 8))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    mse = nn.MSELoss()
    return ParallelTrainer(net, opt, lambda o, t: mse(o, t),
                           mesh=mesh, quant_collectives=quant, **kw)


_BATCH = (np.random.RandomState(0).randn(32, 32).astype('f4'),
          np.random.RandomState(1).randn(32, 8).astype('f4'))


class TestTrainerQuantWire:
    def test_losses_track_full_width(self, mesh):
        tr_f = _make_trainer(mesh, None)
        tr_q = _make_trainer(mesh, {'min_bytes': 0})
        lf = [float(np.asarray(tr_f.step(*_BATCH))) for _ in range(8)]
        lq = [float(np.asarray(tr_q.step(*_BATCH))) for _ in range(8)]
        assert tr_q._quant_active is not None
        # same trajectory within quantization noise, same direction
        assert lq[-1] < lq[0]
        assert abs(lq[-1] - lf[-1]) < 0.02 * abs(lf[0] - lf[-1]) + 1e-3

    def test_census_s8_wire_and_reduction(self, mesh):
        from paddle_tpu.analysis import hlo as _hlo
        tr_f = _make_trainer(mesh, None)
        tr_q = _make_trainer(mesh, {'min_bytes': 0})
        tr_f.step(*_BATCH)
        tr_q.step(*_BATCH)

        def census(tr):
            return _hlo.collective_census(
                _hlo.parse_module(tr.compiled_text()),
                mesh_shape=dict(mesh.shape))

        cf, cq = census(tr_f), census(tr_q)
        assert cf['all-reduce']['wire_dtype'] == 'f32'
        assert cq['all-to-all']['wire_dtype'] == 's8'
        assert cq['all-gather']['wire_dtype'] == 's8'
        wf = sum(r['wire_bytes'] for r in cf.values())
        wq = sum(r['wire_bytes'] for r in cq.values())
        assert wf >= 2 * wq, (wf, wq)

    def test_sync_free_under_transfer_guard(self, mesh):
        tr = _make_trainer(mesh, {'min_bytes': 0}, donate=False)
        tr.step(*_BATCH)        # compile + census outside the guard
        with jax.transfer_guard_device_to_host('disallow'):
            for _ in range(3):
                tr.step(*_BATCH)

    def test_stochastic_keys_in_module_not_host_stream(self, mesh):
        # the quantized trainer consumes EXACTLY as many host rng keys
        # as the full-width one: SR keys derive from the step counter
        from paddle_tpu.core import rng as rng_mod
        tr = _make_trainer(mesh, {'min_bytes': 0})
        paddle.seed(123)
        k_before = np.asarray(rng_mod.next_key())
        paddle.seed(123)
        tr.step(*_BATCH)
        tr.step(*_BATCH)
        k_after = np.asarray(rng_mod.next_key())
        paddle.seed(123)
        rng_mod.next_key(); rng_mod.next_key()
        assert np.array_equal(k_after, np.asarray(rng_mod.next_key()))
        del k_before

    def test_nan_guard_composes(self, mesh):
        tr = _make_trainer(mesh, {'min_bytes': 0}, nan_guard=True)
        loss = tr.step(*_BATCH)
        assert np.isfinite(float(np.asarray(loss)))
        assert tr._step_no == 1
        bad = (np.full_like(_BATCH[0], np.nan), _BATCH[1])
        tr.step(*bad)
        assert tr._step_no == 1     # skipped, params kept finite
        loss = tr.step(*_BATCH)
        assert np.isfinite(float(np.asarray(loss)))

    def test_fused_steps_compose(self, mesh):
        tr = _make_trainer(mesh, {'min_bytes': 0}, fused_steps=4)
        stacked = tuple(np.broadcast_to(a, (4,) + a.shape).copy()
                        for a in _BATCH)
        losses = np.asarray(tr.step_fused(*stacked))
        assert losses.shape == (4,)
        assert np.all(np.isfinite(losses))
        assert tr._quant_active is not None

    def test_no_mesh_degrades_with_warning(self):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        mse = nn.MSELoss()
        tr = ParallelTrainer(net, opt, lambda o, t: mse(o, t),
                             mesh=None,
                             quant_collectives={'min_bytes': 0})
        x = np.random.RandomState(0).randn(4, 8).astype('f4')
        y = np.random.RandomState(1).randn(4, 4).astype('f4')
        with pytest.warns(RuntimeWarning, match='full width'):
            tr.step(x, y)
        assert tr._quant_active is None

    def test_gradient_merge_degrades(self, mesh):
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.gradient_merge = True
        strategy.gradient_merge_configs = {'k_steps': 2}
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(32, 8))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        mse = nn.MSELoss()
        tr = ParallelTrainer(net, opt, lambda o, t: mse(o, t),
                             mesh=mesh, strategy=strategy,
                             quant_collectives={'min_bytes': 0})
        with pytest.warns(RuntimeWarning, match='gradient_merge'):
            tr.step(*_BATCH)
        assert tr._quant_active is None

    def test_zero2_degrades(self, mesh):
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {'stage': 2}
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(32, 8))
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        mse = nn.MSELoss()
        tr = ParallelTrainer(net, opt, lambda o, t: mse(o, t),
                             mesh=mesh, strategy=strategy,
                             quant_collectives={'min_bytes': 0})
        with pytest.warns(RuntimeWarning, match='ZeRO-2'):
            tr.step(*_BATCH)
        assert tr._quant_active is None

    def test_env_default_off(self, mesh, monkeypatch):
        monkeypatch.delenv('PADDLE_TPU_QUANT_COLLECTIVES',
                           raising=False)
        tr = _make_trainer(mesh, None)
        tr.step(*_BATCH)
        assert tr._quant_active is None
        assert 's8[' not in tr.compiled_text()

    def test_explicit_false_beats_armed_env(self, mesh, monkeypatch):
        # the convergence harness's full-width BASELINE depends on
        # this: an ambient env must not quantize a quant=False run
        monkeypatch.setenv('PADDLE_TPU_QUANT_COLLECTIVES',
                           'int8,min_bytes=0')
        tr = _make_trainer(mesh, False)
        tr.step(*_BATCH)
        assert tr._quant_active is None
        tr2 = _make_trainer(mesh, None)     # None -> env decides
        tr2.step(*_BATCH)
        assert tr2._quant_active is not None


class TestLocalSGDQuant:
    def test_quantized_model_average(self, mesh):
        def make(q):
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(32, 64), nn.ReLU(),
                                nn.Linear(64, 8))
            opt = paddle.optimizer.Adam(
                learning_rate=1e-3, parameters=net.parameters())
            mse = nn.MSELoss()
            return LocalSGDTrainer(net, opt, lambda o, t: mse(o, t),
                                   mesh=mesh, k_steps=2,
                                   quant_collectives=q)
        t_f, t_q = make(None), make({'min_bytes': 0})
        lf = [float(np.asarray(t_f.step(*_BATCH))) for _ in range(4)]
        lq = [float(np.asarray(t_q.step(*_BATCH))) for _ in range(4)]
        assert abs(lq[-1] - lf[-1]) < 0.05 * abs(lf[0]) + 1e-3
        # after sync every replica row is identical
        t_q.sync()
        leaf = np.asarray(
            next(iter(jax.tree_util.tree_leaves(t_q.params))))
        for r in range(1, 8):
            assert np.array_equal(leaf[0], leaf[r])


# =============================================================================
# host wire (HostCollectives)
# =============================================================================

class TestHostQuantWire:
    def _pair(self, tmp_path, **kw):
        from paddle_tpu.distributed.collective import (FileKVStore,
                                                       HostCollectives)
        kv = FileKVStore(str(tmp_path / 'kv'))
        mk = lambda r: HostCollectives(  # noqa: E731
            client=kv, rank=r, world=2, timeout_s=15,
            quant='int8', quant_min_bytes=0, **kw)
        return mk(0), mk(1)

    def test_bitwise_equal_across_ranks_and_replay(self, tmp_path):
        import threading
        t0, t1 = self._pair(tmp_path)
        a0 = np.random.RandomState(0).randn(2048).astype('f4')
        a1 = np.random.RandomState(1).randn(2048).astype('f4')
        got = {}
        th = threading.Thread(target=lambda: got.update(
            r0=t0.allreduce(a0, 'mean', tag='s1')))
        th.start()
        r1 = t1.allreduce(a1, 'mean', tag='s1')
        th.join()
        assert np.array_equal(got['r0'], r1)
        assert np.abs(r1 - (a0 + a1) / 2).max() < 0.05
        # a restarted rank re-fetching the same step tag reproduces
        # the identical result (replay-stable quantized wire)
        from paddle_tpu.distributed.collective import HostCollectives
        t0b = HostCollectives(client=t0.client, rank=0, world=2,
                              timeout_s=15, quant='int8',
                              quant_min_bytes=0)
        assert np.array_equal(
            t0b.allreduce(a0, 'mean', tag='s1'), r1)

    def test_allgather_stays_exact_under_instance_quant(self,
                                                        tmp_path):
        import threading
        t0, t1 = self._pair(tmp_path)
        a0 = np.random.RandomState(0).randn(2048).astype('f4')
        a1 = np.random.RandomState(1).randn(2048).astype('f4')
        got = {}
        th = threading.Thread(target=lambda: got.update(
            r0=t0.allgather(a0, tag='g1')))
        th.start()
        r1 = t1.allgather(a1, tag='g1')
        th.join()
        # gathers exchange EXACT state: the lossy instance default
        # must not apply
        assert np.array_equal(r1[0], a0)
        assert np.array_equal(r1[1], a1)
        assert np.array_equal(got['r0'], r1)

    def test_quant_frame_smaller_and_ints_pass_through(self, tmp_path):
        from paddle_tpu.distributed.collective import (_frame,
                                                       _frame_quant)
        a = np.random.RandomState(0).randn(4096).astype('f4')
        assert len(_frame_quant(a)) < len(_frame(a)) / 2
        t0, _ = self._pair(tmp_path)
        # int payloads are not floats: quantization must not touch them
        assert not t0._use_quant(np.arange(4096, dtype=np.int64), None)
        assert t0._use_quant(a, None)
        assert not t0._use_quant(a, False)

    def test_corrupt_after_crc_rejected(self, tmp_path):
        from paddle_tpu.distributed.collective import (
            CollectivePayloadError, _frame_quant, _unframe)
        p = _frame_quant(np.random.RandomState(0).randn(512)
                         .astype('f4'))
        for flip_at in (-1, len(p) - 100):
            b = bytearray(p)
            b[flip_at] ^= 0xFF
            with pytest.raises(CollectivePayloadError):
                _unframe(bytes(b), 'allreduce-mean', 't', 0)

    def test_corrupt_seam_rejected_end_to_end(self, tmp_path):
        import threading
        from paddle_tpu.distributed.collective import (
            CollectivePayloadError)
        from paddle_tpu.resilience.chaos import ChaosEngine, FaultPlan
        t0, t1 = self._pair(tmp_path)
        eng = ChaosEngine(FaultPlan(seed=0, faults=[
            {'kind': 'collective_corrupt', 'at_step': 1,
             'rank': 0}]), rank=0).activate()
        try:
            eng.step(1)
            arr = np.random.RandomState(0).randn(512).astype('f4')
            th = threading.Thread(
                target=lambda: self._swallow(
                    lambda: t0.allreduce(arr, 'mean', tag='c1')))
            th.start()
            with pytest.raises(CollectivePayloadError):
                t1.allreduce(arr, 'mean', tag='c1')
            th.join()
        finally:
            eng.deactivate()

    @staticmethod
    def _swallow(fn):
        try:
            fn()
        except Exception:
            pass


# =============================================================================
# packed int4 (PTQ backend)
# =============================================================================

class TestPackedInt4:
    def test_pack_unpack_lossless(self):
        from paddle_tpu.ops.int8_matmul import (
            quantize_weight_int4_packed, unpack_int4)
        for H in (16, 17, 1):
            w = np.random.RandomState(H).randn(H, 12).astype('f4')
            packed, s = quantize_weight_int4_packed(w)
            q = unpack_int4(packed, H)
            ref = jnp.clip(jnp.round(jnp.asarray(w) / s[None]),
                           -7, 7).astype(jnp.int8)
            assert jnp.array_equal(q, ref)
            assert packed.shape[0] == (H + 1) // 2

    def test_matmul_parity_vs_int8_path(self):
        from paddle_tpu.ops.int8_matmul import (
            quantize_weight_int4_packed, unpack_int4,
            dynamic_int4_matmul, dynamic_int8_matmul)
        rs = np.random.RandomState(0)
        w = rs.randn(33, 16).astype('f4')
        x = rs.randn(4, 33).astype('f4')
        packed, s = quantize_weight_int4_packed(w)
        out4 = dynamic_int4_matmul(x, packed, s, rows=33,
                                   out_dtype=jnp.float32)
        out8 = dynamic_int8_matmul(
            x, np.asarray(unpack_int4(packed, 33)), s,
            out_dtype=jnp.float32)
        assert jnp.array_equal(out4, out8)

    def test_int4_linear_close_to_float(self):
        from paddle_tpu.quantization import Int4DynamicLinear
        paddle.seed(0)
        lin = nn.Linear(64, 32)
        q = Int4DynamicLinear(lin)
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(4, 64).astype('f4'))
        ref = np.asarray(lin(x).value)
        got = np.asarray(q(x).value).astype('f4')
        denom = np.abs(ref).mean()
        assert np.abs(got - ref).mean() / denom < 0.2

    def test_quantize_for_serving_modes(self):
        from paddle_tpu.quantization import (
            quantize_for_serving, Int8DynamicLinear, Int4DynamicLinear)
        for mode, cls in (('int8', Int8DynamicLinear),
                          ('int4', Int4DynamicLinear)):
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(16, 16), nn.ReLU(),
                                nn.Linear(16, 4))
            quantize_for_serving(net, mode)
            kinds = [type(s) for _, s in net.named_sublayers()]
            assert kinds.count(cls) == 2
        with pytest.raises(ValueError):
            quantize_for_serving(nn.Sequential(nn.Linear(4, 4)),
                                 'int2')

    def test_engine_refuses_mode_mismatch_on_quantized_model(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, GPTConfig
        from paddle_tpu.serving import ServingEngine, ServeConfig
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        from paddle_tpu.quantization import quantize_for_serving
        quantize_for_serving(m, 'int8')
        assert m._ptq_mode == 'int8'
        # the swap dropped float weights: a full-width (or int4)
        # config on this model would compile a mis-keyed surface
        with pytest.raises(ValueError, match='already PTQ-quantized'):
            ServingEngine(m, ServeConfig(max_slots=2,
                                         prompt_buckets=(8,),
                                         max_model_len=32))
        with pytest.raises(ValueError, match='already PTQ-quantized'):
            ServingEngine(m, ServeConfig(max_slots=2, quantize='int4',
                                         prompt_buckets=(8,),
                                         max_model_len=32))
        # the MATCHING mode is idempotent (rebuild from the same model)
        ServingEngine(m, ServeConfig(max_slots=2, quantize='int8',
                                     prompt_buckets=(8,),
                                     max_model_len=32))

    def test_serve_config_quantize_keys_signature(self):
        from paddle_tpu.serving import ServeConfig
        a = ServeConfig(max_slots=4).signature()
        b = ServeConfig(max_slots=4, quantize='int8').signature()
        c = ServeConfig(max_slots=4, quantize='int4').signature()
        assert len({a, b, c}) == 3
        with pytest.raises(ValueError):
            ServeConfig(quantize='fp8')


# =============================================================================
# cost model / census / planner wire-dtype dimension
# =============================================================================

class TestWireDtypeDimension:
    def test_quant_wire_factor_and_cost(self):
        from paddle_tpu.analysis import costmodel as cm
        f = cm.quant_wire_factor(4, 'int8', 256)
        assert abs(f - (1 + 4 / 256) / 4) < 1e-9
        full = cm.torus_cost('all-reduce', 1 << 20, (('dp', 8),))
        q = cm.quantized_allreduce_cost(1 << 20, (('dp', 8),))
        assert q['wire_dtype'] == 'int8'
        # ~4x fewer bytes than the full-width all-reduce
        assert full['wire_bytes'] > 3.5 * q['wire_bytes']
        m = cm.quantized_allreduce_cost(1 << 20, (('dp', 8),),
                                        master_accum=True)
        # master accumulation: full-width reduce half dominates
        assert m['wire_bytes'] > q['wire_bytes']
        with pytest.raises(ValueError):
            cm.quant_wire_factor(4, 'fp7')

    def test_census_tags_wire_dtype(self, mesh):
        tr = _make_trainer(mesh, {'min_bytes': 0})
        tr.step(*_BATCH)
        from paddle_tpu.analysis import hlo as _hlo
        idx = _hlo.collective_instrs(
            _hlo.parse_module(tr.compiled_text()),
            mesh_shape=dict(mesh.shape))
        dtypes = {}
        for r in idx.values():
            dtypes.setdefault(r['op'], set()).add(r['wire_dtype'])
        # the payload all-to-all is s8; its scale twin rides as f32
        assert 's8' in dtypes.get('all-to-all', set())
        # the census aggregation tags the op by its byte-dominant call
        cen = _hlo.collective_census(
            _hlo.parse_module(tr.compiled_text()),
            mesh_shape=dict(mesh.shape))
        assert cen['all-to-all']['wire_dtype'] == 's8'

    def test_planner_recommends_quant_when_ar_dominates(self):
        from paddle_tpu.analysis import planner as pl
        from paddle_tpu.analysis import hlo as _hlo
        plan = pl.ShardingPlan({'dp': 8}, 'replicated')
        plan.census = {'all-reduce': {
            'calls': 1, 'bytes': 8 << 20, 'wire_bytes': 14 << 20,
            'est_us': 900.0, 'phases': 14, 'group_size': 8,
            'axes': (('dp', 8),), 'wire_dtype': 'f32',
            'max_wire_bytes': 14 << 20, 'max_est_us': 900.0,
            'file': None, 'line': None}}
        plan.wire_bytes = 14 << 20
        plan.est_us = 900.0
        plan.compute_us = 100.0
        plan.score_us = 1000.0
        pl._maybe_recommend_quant(plan, _hlo.DEFAULT_HLO_THRESHOLDS)
        assert plan.quant is not None
        assert plan.quant['recommended'] is True
        assert plan.quant['score_us'] < plan.score_us
        assert plan.to_json()['quant']['wire_dtype'] == 'int8'
        # an s8 census row must NOT re-recommend
        plan2 = pl.ShardingPlan({'dp': 8}, 'replicated')
        plan2.census = {'all-reduce': dict(
            plan.census['all-reduce'], wire_dtype='s8')}
        plan2.est_us = plan2.score_us = 900.0
        pl._maybe_recommend_quant(plan2, _hlo.DEFAULT_HLO_THRESHOLDS)
        assert plan2.quant is None

    def test_collective_cost_event_tagged(self, mesh, tmp_path):
        from paddle_tpu import telemetry
        telemetry.enable(str(tmp_path / 'tel'))
        try:
            tr = _make_trainer(mesh, {'min_bytes': 0})
            tr.step(*_BATCH)
            events = telemetry.events('collective_cost')
            assert events
            last = events[-1]
            assert last['quant_collectives'] == 'int8'
            assert last['per_op']['all-to-all']['wire_dtype'] == 's8'
        finally:
            telemetry.disable()


    def test_lenet_gate_and_observed_join(self, tmp_path):
        """tools/quant_accuracy.compare on lenet, dp=8, a profile
        window in the quantized run: it converges within a tenth of
        full width's loss progress at under half the predicted wire
        bytes, compiling once, and run_report joins the window's
        s8-tagged collective_observed rows to collectives_cmp."""
        import importlib.util
        import os
        from paddle_tpu import telemetry

        def tool(name):
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(os.path.dirname(__file__), '..',
                                   'tools', f'{name}.py'))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod

        d = str(tmp_path / 'tel')
        telemetry.enable(d)
        try:
            row = tool('quant_accuracy').compare(
                'lenet', {'block': 256, 'min_bytes': 0}, steps=25,
                profile={'every': 100, 'steps': 2, 'start': 2,
                         'dir': d})
            observed = telemetry.events('collective_observed')
        finally:
            telemetry.disable()
        assert row['loss_delta_rel'] <= 0.10, row
        assert row['wire_reduction'] >= 2.0, row
        assert [op for op, r in row['census_quant'].items()
                if r.get('wire_dtype') == 's8']
        assert row['compile_events_quant'] == 1
        assert [e for e in observed if e.get('wire_dtype') == 's8']
        rr = tool('run_report')
        events, sources, skew = rr.load_events(*rr.discover([d]))
        tagged = [r for r in rr.analyze(events, sources, skew)[
            'collectives_cmp'].values() if r.get('wire_dtype') == 's8']
        assert tagged and any(r.get('observed_us') for r in tagged)


# =============================================================================
# property sweeps over the pure cores (cheap, wide coverage)
# =============================================================================

class TestQuantCoreProperties:
    @pytest.mark.parametrize('block', [32, 64, 128, 256, 512])
    @pytest.mark.parametrize('mult', [1, 3, 10])
    def test_round_trip_stable_across_blocks(self, block, mult):
        x = jnp.asarray(
            np.random.RandomState(block + mult).randn(block * mult),
            jnp.float32)
        q, s = qc.quantize_blocks(x, block)
        d = qc.dequantize_blocks(q, s)
        q2, _ = qc.quantize_blocks(d, block, scales=s)
        assert jnp.array_equal(q, q2)
        assert s.shape == (mult,)

    @pytest.mark.parametrize('seed', list(range(8)))
    def test_stochastic_replay_across_keys(self, seed):
        x = jnp.asarray(np.random.RandomState(seed).randn(512),
                        jnp.float32)
        k = jax.random.PRNGKey(seed)
        qa, sa = qc.quantize_blocks(x, 128, key=k)
        qb, sb = qc.quantize_blocks(x, 128, key=k)
        assert jnp.array_equal(qa, qb)
        assert jnp.array_equal(sa, sb)

    @pytest.mark.parametrize('seed', list(range(10)))
    def test_host_quantizer_pure_and_bounded(self, seed):
        from paddle_tpu.distributed.collective import (_quantize_host,
                                                       _frame_quant,
                                                       _unframe)
        a = np.random.RandomState(seed).randn(777).astype('f4') \
            * (10.0 ** (seed % 5 - 2))
        qa, sa = _quantize_host(a)
        qb, sb = _quantize_host(a)
        assert np.array_equal(qa, qb) and np.array_equal(sa, sb)
        back = _unframe(_frame_quant(a), 'op', 't', 0)
        assert back.shape == a.shape and back.dtype == a.dtype
        # per-block abs-max grid: error under one grid cell everywhere
        assert np.all(np.abs(back - a)
                      <= sa.max() * 0.5 * (1 + 1e-6) + 1e-12)

    @pytest.mark.parametrize('H', list(range(1, 13)))
    def test_int4_pack_round_trip_rows(self, H):
        from paddle_tpu.ops.int8_matmul import (
            quantize_weight_int4_packed, unpack_int4)
        w = np.random.RandomState(H).randn(H, 6).astype('f4')
        packed, s = quantize_weight_int4_packed(w)
        q = unpack_int4(packed, H)
        assert q.shape == (H, 6)
        assert int(jnp.abs(q).max()) <= 7
        d = np.asarray(q, dtype='f4') * np.asarray(s)[None, :]
        assert np.abs(d - w).max() <= float(np.asarray(s).max()) \
            * 0.5 * (1 + 1e-6)

    @pytest.mark.parametrize('spec,expect', [
        ('int8', {'dtype': 'int8'}),
        ('1', {'dtype': 'int8'}),
        ('true', {'dtype': 'int8'}),
        ('int8,block=64', {'block': 64}),
        ('int8,min_bytes=0', {'min_bytes': 0}),
        ('int8,seed=42', {'seed': 42}),
        ('int8,stochastic=false', {'stochastic': False}),
        ('int8,master_accum=yes', {'master_accum': True}),
        ('block=128,master_accum=0', {'block': 128,
                                      'master_accum': False}),
        ('dtype=int8,block=32', {'block': 32}),
    ])
    def test_env_grammar(self, spec, expect):
        got = resolve_quant_collectives(None, env=spec)
        assert got is not None
        for k, v in expect.items():
            assert getattr(got, k) == v

    @pytest.mark.parametrize('off', ['', '0', 'off', 'false', 'none',
                                     'no'])
    def test_env_grammar_off(self, off):
        assert resolve_quant_collectives(None, env=off) is None

    @pytest.mark.parametrize('dtype,elem,factor', [
        ('int8', 4, (1 + 4 / 256) / 4),
        ('int8', 2, (1 + 4 / 256) / 2),
        ('int4', 4, (0.5 + 4 / 256) / 4),
        ('bf16', 4, (2 + 4 / 256) / 4),
    ])
    def test_wire_factor_table(self, dtype, elem, factor):
        from paddle_tpu.analysis import costmodel as cm
        assert abs(cm.quant_wire_factor(elem, dtype, 256)
                   - factor) < 1e-9

    @pytest.mark.parametrize('n', [2, 4, 8, 16])
    def test_quantized_cost_scales_with_group(self, n):
        from paddle_tpu.analysis import costmodel as cm
        full = cm.torus_cost('all-reduce', 1 << 20, (('dp', n),))
        q = cm.quantized_allreduce_cost(1 << 20, (('dp', n),))
        assert 0 < q['wire_bytes'] < full['wire_bytes']
        assert q['est_us'] < full['est_us']


# =============================================================================
# chaos / soak coverage class
# =============================================================================

class TestQuantSoakCoverage:
    def test_plangen_quant_wire_tag_same_faults(self):
        from paddle_tpu.resilience import plangen
        a = plangen.generate_plan(7, 12, 2)
        b = plangen.generate_plan(7, 12, 2, quant_wire=True)
        assert b.name.endswith('+qwire')
        assert [f.to_dict() for f in a.faults] == \
            [f.to_dict() for f in b.faults]

    def test_final_w_quant_reference_pure(self):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            'soak_run', os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                'tools', 'soak_run.py'))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        a = mod._final_w(12, world=2, quant=True)
        b = mod._final_w(12, world=2, quant=True)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, mod._final_w(12, world=2))
