"""Whole-loop compilation (core.scan_loop): K-step fused train loops.

Pins the fused-chunk contracts:
* bit-exactness of fused vs unfused (losses, params, rng stream) at
  K=1 and K=8 on both wired loops (hapi.Model.fit and
  ParallelTrainer);
* ONE host sync per K-chunk (transfer-guard proof: the loops run
  under ``transfer_guard_device_to_host('disallow')`` and only the
  sanctioned ``scan_loop.chunk_sync`` escape fires, exactly once);
* a NaN-injected step inside a chunk rolls back (the in-scan
  ``lax.cond`` carry keeps the poisoned update out) and the step
  counter stays exact;
* preemption/restore granularity is the chunk boundary;
* the fused module rides the persistent compile cache under a
  K-folded fingerprint (warm start);
* StepAccumulator chunk rows expand to per-step stats, profiler
  windows land on exact chunk-aligned step ids, and the chunk-break
  lint rule flags host callbacks only under declared fused intent.

Sorts before tests/test_host_embedding.py (the seed's known abort).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import scan_loop
from paddle_tpu.parallel import ParallelTrainer


def make_mlp_trainer(fused=None, nan_guard=False, seed=0, **kw):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    ce = nn.CrossEntropyLoss()
    return ParallelTrainer(net, opt, lambda o, t: ce(o, t),
                           fused_steps=fused, nan_guard=nan_guard,
                           **kw)


def batch_data(k, b=16, d=8, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    xs = rs.randn(k, b, d).astype('float32')
    ys = rs.randint(0, classes, size=(k, b, 1)).astype('int64')
    return xs, ys


# -- knobs --------------------------------------------------------------------

class TestResolve:
    def test_explicit_wins(self):
        assert scan_loop.resolve_fused_steps(8) == 8
        assert scan_loop.resolve_fused_steps(0) == 0
        assert scan_loop.resolve_fused_steps(False) == 0
        assert scan_loop.resolve_fused_steps('16') == 16

    def test_env_default(self, monkeypatch):
        monkeypatch.setenv(scan_loop.ENV_VAR, '32')
        assert scan_loop.resolve_fused_steps(None) == 32
        monkeypatch.setenv(scan_loop.ENV_VAR, 'off')
        assert scan_loop.resolve_fused_steps(None) == 0
        monkeypatch.delenv(scan_loop.ENV_VAR)
        assert scan_loop.resolve_fused_steps(None) == 0
        # explicit beats env
        monkeypatch.setenv(scan_loop.ENV_VAR, '32')
        assert scan_loop.resolve_fused_steps(4) == 4
        assert scan_loop.resolve_fused_steps(False) == 0

    def test_negative_rejected(self):
        with pytest.raises(ValueError):
            scan_loop.resolve_fused_steps(-1)

    @pytest.mark.parametrize('arg,want', [
        (0, 0), (1, 1), (2, 2), (8, 8), (32, 32), (1024, 1024),
        ('0', 0), ('1', 1), ('8', 8), ('  32 ', 32),
        ('off', 0), ('OFF', 0), ('false', 0), ('none', 0),
        ('no', 0), ('', 0), (True, 1), (False, 0),
    ])
    def test_parse_table(self, arg, want):
        assert scan_loop.resolve_fused_steps(arg) == want

    @pytest.mark.parametrize('env,want', [
        ('0', 0), ('8', 8), ('off', 0), ('false', 0), ('32', 32),
    ])
    def test_env_table(self, monkeypatch, env, want):
        monkeypatch.setenv(scan_loop.ENV_VAR, env)
        assert scan_loop.resolve_fused_steps(None) == want

    @pytest.mark.parametrize('k,step_s,est,want', [
        (32, 1.0, 0.3, 3), (32, 1.0, 1.0, 1), (32, 1.0, None, 32),
        (32, 10.0, 0.3, 32), (32, 0.1, 5.0, 1), (8, 4.0, 0.5, 8),
        (8, 2.0, 0.5, 4), (1, 1.0, 0.3, 1), (16, 1.6, 0.2, 8),
        (0, 1.0, 0.3, 1),
    ])
    def test_clamp_table(self, k, step_s, est, want):
        from paddle_tpu.resilience.watchdog import Budget
        assert scan_loop.clamp_chunk(
            k, Budget(step_s=step_s), est_step_s=est) == want

    def test_clamp_chunk(self):
        from paddle_tpu.resilience.watchdog import Budget
        # no budget / no estimate -> passthrough
        assert scan_loop.clamp_chunk(32) == 32
        assert scan_loop.clamp_chunk(32, Budget(step_s=1.0)) == 32
        # chunk must fit inside the armed per-step deadline
        assert scan_loop.clamp_chunk(
            32, Budget(step_s=1.0), est_step_s=0.3) == 3
        # never below 1, even when one step already blows the budget
        assert scan_loop.clamp_chunk(
            32, Budget(step_s=0.1), est_step_s=5.0) == 1
        # a derived budget (step_s=None) never clamps
        assert scan_loop.clamp_chunk(
            32, Budget(), est_step_s=0.3) == 32

    def test_stack_batches(self):
        b1 = (np.ones((4, 3)), np.zeros((4, 1)))
        b2 = (np.full((4, 3), 2.0), np.ones((4, 1)))
        xs, ys = scan_loop.stack_batches([b1, b2])
        assert xs.shape == (2, 4, 3) and ys.shape == (2, 4, 1)
        assert float(xs[1, 0, 0]) == 2.0

    def test_stack_batches_device_leaves_no_readback(self):
        # already-staged device batches stack ON DEVICE — under a
        # d2h transfer guard, so a hidden np.asarray would raise
        b1 = (jnp.ones((4, 3)),)
        b2 = (jnp.full((4, 3), 2.0),)
        with jax.transfer_guard_device_to_host('disallow'):
            (xs,) = scan_loop.stack_batches([b1, b2])
        assert isinstance(xs, jax.Array) and xs.shape == (2, 4, 3)


class TestChunkPrefetcher:
    def _batches(self, n):
        return [(np.full((2,), i, 'float32'),) for i in range(n)]

    @pytest.mark.parametrize('background', [False, True])
    def test_chunks_and_tail(self, background):
        seen = []

        def stage(batches):
            return scan_loop.stack_batches(batches)

        pref = scan_loop.ChunkPrefetcher(
            self._batches(10), 4, stage, background=background)
        for staged, n, wait_s in pref:
            seen.append(n)
            if n == 4:
                (xs,) = staged
                assert xs.shape == (4, 2)
            else:
                # tail arrives UNSTAGED for the per-step path
                assert isinstance(staged, list) and len(staged) == n
        assert seen == [4, 4, 2]

    def test_producer_error_surfaces(self):
        def bad_iter():
            yield (np.zeros(2),)
            raise RuntimeError('loader died')

        pref = scan_loop.ChunkPrefetcher(
            bad_iter(), 2, scan_loop.stack_batches, background=True)
        with pytest.raises(RuntimeError, match='loader died'):
            list(pref)


# -- trainer bit-exactness ----------------------------------------------------

class TestTrainerFused:
    @pytest.mark.parametrize('k', [1, 8])
    def test_bit_exact_vs_unfused(self, k):
        from paddle_tpu.core import rng as rng_mod
        xs, ys = batch_data(k)
        t1 = make_mlp_trainer()
        losses1 = [np.asarray(t1.step(xs[i], ys[i]))
                   for i in range(k)]
        key_after_1 = np.asarray(rng_mod.get_cuda_rng_state()[0])

        t2 = make_mlp_trainer(fused=k)
        losses2 = np.asarray(t2.step_fused(xs, ys))
        key_after_2 = np.asarray(rng_mod.get_cuda_rng_state()[0])

        # losses, params AND the host rng stream are bit-identical
        assert np.array_equal(np.asarray(losses1), losses2)
        for n in t1.params:
            assert np.array_equal(np.asarray(t1.params[n]),
                                  np.asarray(t2.params[n])), n
        for n in t1.opt_state:
            for s, v in t1.opt_state[n].items():
                assert np.array_equal(
                    np.asarray(v), np.asarray(t2.opt_state[n][s])), \
                    (n, s)
        assert np.array_equal(key_after_1, key_after_2)
        assert t1._step_no == t2._step_no == k

    def test_conv_model_k1_within_reassociation_tolerance(self):
        """A length-1 scan over a conv model is the per-step loop up
        to XLA reassociating the conv gradient inside the scan body
        (about 1 ULP a step): allclose where the dense model above is
        bit-exact."""
        from paddle_tpu.vision.models import LeNet
        rs = np.random.RandomState(0)
        x = rs.randn(4, 1, 28, 28).astype('float32')
        y = rs.randint(0, 10, size=(4, 1)).astype('int64')

        def make(fused):
            paddle.seed(0)
            net = LeNet()
            opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=net.parameters())
            ce = nn.CrossEntropyLoss()
            return ParallelTrainer(net, opt, lambda o, t: ce(o, t),
                                   fused_steps=fused)

        t1, t2 = make(0), make(1)
        per_step = [np.asarray(t1.step(x, y)) for _ in range(3)]
        fused = [np.asarray(t2.step_fused(x[None], y[None]))[0]
                 for _ in range(3)]
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(per_step),
                                   rtol=1e-5, atol=1e-6)

    def test_nan_injected_chunk_rolls_back(self):
        k = 4
        xs, ys = batch_data(k)
        xs[2] = np.nan      # poison step index 2 of the chunk
        t1 = make_mlp_trainer(nan_guard=True)
        for i in range(k):
            t1.step(xs[i], ys[i])
        t2 = make_mlp_trainer(fused=k, nan_guard=True)
        losses = t2.step_fused(xs, ys)
        # the poisoned step was skipped on device in BOTH loops:
        # params bit-equal, counter advanced k-1, loss[2] non-finite
        assert not np.isfinite(np.asarray(losses)[2])
        assert t1._step_no == t2._step_no == k - 1
        for n in t1.params:
            assert np.array_equal(np.asarray(t1.params[n]),
                                  np.asarray(t2.params[n])), n
        for n, v in t2.params.items():
            assert np.all(np.isfinite(np.asarray(v))), n
        assert t2.sentinel.total_skipped == 1

    def test_one_host_sync_per_chunk(self):
        from paddle_tpu import telemetry
        k = 8
        xs, ys = batch_data(k)
        t = make_mlp_trainer(fused=k, nan_guard=True)
        t.step_fused(xs, ys)    # compile outside the guard
        rec = telemetry.get_recorder()
        before = rec.counters.get('fused.chunk_syncs', 0)
        # the WHOLE steady-state chunk runs under device->host
        # disallow: only the sanctioned chunk_sync escape may read,
        # and it fires exactly once
        with jax.transfer_guard_device_to_host('disallow'):
            t.step_fused(xs, ys)
        assert rec.counters.get('fused.chunk_syncs', 0) - before == 1

    def test_zero_syncs_without_guard(self):
        k = 8
        xs, ys = batch_data(k)
        t = make_mlp_trainer(fused=k)
        t.step_fused(xs, ys)
        with jax.transfer_guard_device_to_host('disallow'):
            losses = t.step_fused(xs, ys)
        # losses stayed device arrays; materializing now is on us
        assert np.asarray(losses).shape == (k,)

    def test_restore_lands_on_chunk_boundary(self, tmp_path):
        k = 4
        xs, ys = batch_data(k)
        t = make_mlp_trainer(fused=k)
        t.step_fused(xs, ys)
        t.step_fused(xs, ys)            # step 8: a chunk boundary
        t.save_checkpoint(str(tmp_path), async_save=False)
        saved = {n: np.asarray(v) for n, v in t.params.items()}
        t.step_fused(xs, ys)            # step 12 (pretend mid-flight)
        got = t.restore_checkpoint(str(tmp_path))
        assert got == 8 and t._step_no == 8
        for n, v in saved.items():
            assert np.array_equal(v, np.asarray(t.params[n])), n

    def test_watchdog_clamp_warns(self):
        from types import SimpleNamespace
        from paddle_tpu.resilience.watchdog import Budget
        k = 32
        xs, ys = batch_data(k)
        t = make_mlp_trainer(fused=k,
                             watchdog=Budget(step_s=0.2))
        # a plan estimate of 0.1 s/step fits only 2 steps in the
        # armed 0.2 s deadline -> staging a 32-chunk warns
        t.plan = SimpleNamespace(est_us=50_000, compute_us=50_000)
        try:
            assert t.fused_chunk_len() == 2
            with pytest.warns(RuntimeWarning,
                              match='exceeds the watchdog'):
                t.step_fused(xs, ys)
            assert t._step_no == k      # the chunk still ran whole
        finally:
            t.stop_watchdog()

    def test_chunk_rows_stay_monotone_under_skips(self):
        # nan_guard skips advance _step_no by the finite count only;
        # telemetry rows must still carry unique monotone ids
        k = 4
        xs, ys = batch_data(k)
        xs[1] = np.nan
        t = make_mlp_trainer(fused=k, nan_guard=True)
        t.step_fused(xs, ys)
        assert t._fused_rows == k
        t.step_fused(np.nan_to_num(xs), ys)
        assert t._fused_rows == 2 * k   # not 2k-1: skips don't blur ids

    def test_fused_only_census_text_is_none(self):
        # a fused-only trainer has no per-step module: the profiler's
        # census join must SKIP cleanly, not raise into the window
        k = 2
        xs, ys = batch_data(k)
        t = make_mlp_trainer(fused=k)
        t.step_fused(xs, ys)
        assert t._compiled is None and t._census_text() is None
        from paddle_tpu.telemetry import ProfileSchedule, StepProfiler
        prof = StepProfiler(ProfileSchedule(), hlo_text_fn=t._census_text)

        class _FakeProf:
            def collectives(self):
                return [object()]
        assert prof._match(_FakeProf()) == []

    def test_pipeline_rejected(self):
        t = make_mlp_trainer(fused=4)
        t._pipeline = True
        with pytest.raises(NotImplementedError):
            t.step_fused(np.zeros((4, 2, 8), 'float32'),
                         np.zeros((4, 2, 1), 'int64'))


# -- hapi bit-exactness -------------------------------------------------------

def make_hapi_model(seed=0):
    from paddle_tpu import Model
    from paddle_tpu.metric import Accuracy
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    m = Model(net)
    m.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters()),
              nn.CrossEntropyLoss(), metrics=[Accuracy()])
    return m


def hapi_dataset(n=36, d=8, classes=4, seed=0):
    from paddle_tpu.io import TensorDataset
    rs = np.random.RandomState(seed)
    X = rs.randn(n, d).astype('float32')
    Y = rs.randint(0, classes, size=(n, 1)).astype('int64')
    return TensorDataset([X, Y])


class TestHapiFused:
    @pytest.mark.parametrize('k', [1, 4])
    def test_fit_bit_exact(self, k):
        ds = hapi_dataset()     # 9 batches of 4: 2 chunks + tail @ k=4
        m1 = make_hapi_model()
        m1.fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0)
        m2 = make_hapi_model()
        m2.fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
               fused_steps=k)
        p1, _ = m1.network.functional_state()
        p2, _ = m2.network.functional_state()
        for n in p1:
            assert np.array_equal(np.asarray(p1[n]),
                                  np.asarray(p2[n])), n
        assert m1._optimizer._global_step == \
            m2._optimizer._global_step == 18

    def test_env_var_drives_fit(self, monkeypatch):
        monkeypatch.setenv(scan_loop.ENV_VAR, '4')
        ds = hapi_dataset(n=16)
        m1 = make_hapi_model()
        m1.fit(ds, batch_size=4, epochs=1, shuffle=False, verbose=0,
               fused_steps=False)      # explicit off beats env
        assert not m1._train_chunk_cache
        m2 = make_hapi_model()
        m2.fit(ds, batch_size=4, epochs=1, shuffle=False, verbose=0)
        assert m2._train_chunk_cache   # env turned fusion on
        p1, _ = m1.network.functional_state()
        p2, _ = m2.network.functional_state()
        for n in p1:
            assert np.array_equal(np.asarray(p1[n]),
                                  np.asarray(p2[n])), n

    def test_callbacks_fire_per_chunk(self):
        from paddle_tpu.hapi.callbacks import Callback

        class Cadence(Callback):
            steps = []

            def on_train_batch_end(self, step, logs=None):
                Cadence.steps.append(step)

        Cadence.steps = []
        ds = hapi_dataset(n=16)
        m = make_hapi_model()
        m.fit(ds, batch_size=4, epochs=1, shuffle=False, verbose=0,
              fused_steps=4, callbacks=[Cadence()])
        # 16 samples / batch 4 = 4 steps = 1 chunk -> ONE callback at
        # the chunk's last step index
        assert Cadence.steps == [3]

    def test_stop_training_lands_on_chunk_boundary(self):
        from paddle_tpu.hapi.callbacks import Callback

        class StopAt(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step >= 5:
                    self.model.stop_training = True

        ds = hapi_dataset(n=36)
        m = make_hapi_model()
        m.fit(ds, batch_size=4, epochs=3, shuffle=False, verbose=0,
              fused_steps=4, callbacks=[StopAt()])
        # the stop request lands mid-epoch; training halts at the
        # NEXT chunk boundary: preemption granularity is K steps
        step = m._optimizer._global_step
        assert step == 8 and step % 4 == 0

    def test_one_host_sync_per_chunk(self):
        from paddle_tpu import telemetry
        k = 4
        rs = np.random.RandomState(0)
        xs = rs.randn(k, 4, 8).astype('float32')
        ys = rs.randint(0, 4, size=(k, 4, 1)).astype('int64')
        m = make_hapi_model()
        m.train_chunk((xs, ys), n_in=1, k=k)    # compile
        rec = telemetry.get_recorder()
        before = rec.counters.get('fused.chunk_syncs', 0)
        with jax.transfer_guard_device_to_host('disallow'):
            m.train_chunk((xs, ys), n_in=1, k=k)
        assert rec.counters.get('fused.chunk_syncs', 0) - before == 1

    def test_nan_chunk_registers_strike_despite_finite_tail(self):
        # NanGuard reads _last_step_ok once per chunk: a poisoned
        # step mid-chunk must mark the WHOLE chunk not-ok even when
        # the chunk's last step is finite — otherwise divergence
        # protection silently weakens ~K-fold
        k = 4
        rs = np.random.RandomState(0)
        xs = rs.randn(k, 4, 8).astype('float32')
        ys = rs.randint(0, 4, size=(k, 4, 1)).astype('int64')
        xs[1] = np.nan      # poison a MIDDLE step; tail stays finite
        m = make_hapi_model()
        _, oks = m.train_chunk((xs, ys), n_in=1, k=k)
        assert bool(np.asarray(oks)[-1]) is True
        assert m._last_step_ok is False
        assert m._optimizer._global_step == k - 1

    def test_metrics_match_per_step_feed(self):
        # chunk-merged metric stats == K per-step updates
        k = 4
        rs = np.random.RandomState(0)
        xs = rs.randn(k, 4, 8).astype('float32')
        ys = rs.randint(0, 4, size=(k, 4, 1)).astype('int64')
        m1 = make_hapi_model()
        for i in range(k):
            m1.train_batch(xs[i], ys[i])
        acc1 = m1._metrics[0].accumulate()
        m2 = make_hapi_model()
        m2.train_chunk((xs, ys), n_in=1, k=k)
        acc2 = m2._metrics[0].accumulate()
        assert acc1 == pytest.approx(acc2)


# -- compile cache ------------------------------------------------------------

@pytest.fixture
def cache(tmp_path, monkeypatch):
    from paddle_tpu.core import compile_cache as cc
    d = tmp_path / 'ccache'
    monkeypatch.setenv(cc.ENV_VAR, str(d))
    cc.reset_stats()
    cc._extra_dirs.clear()
    yield str(d)
    cc.reset_stats()
    cc._extra_dirs.clear()


class TestFusedCompileCache:
    def test_warm_start_of_fused_module(self, cache):
        from paddle_tpu.core import compile_cache as cc
        k = 4
        xs, ys = batch_data(k)
        before = cc.stats()
        t1 = make_mlp_trainer(fused=k)
        l1 = np.asarray(t1.step_fused(xs, ys))
        s1 = cc.stats()
        assert s1.get('serialize_exec', 0) - \
            before.get('serialize_exec', 0) >= 1
        # a second trainer with the identical program deserializes
        # the fused module instead of recompiling
        t2 = make_mlp_trainer(fused=k)
        l2 = np.asarray(t2.step_fused(xs, ys))
        s2 = cc.stats()
        assert s2.get('deserialize_exec', 0) - \
            s1.get('deserialize_exec', 0) >= 1
        assert np.array_equal(l1, l2)

    def test_fingerprint_folds_k(self, cache):
        # K=4 and K=8 fused modules must never collide, nor with the
        # per-step module
        k4 = make_mlp_trainer(fused=4)
        xs4, ys4 = batch_data(4)
        k4.step_fused(xs4, ys4)
        fp4 = k4._fused_fp
        k8 = make_mlp_trainer(fused=8)
        xs8, ys8 = batch_data(8)
        k8.step_fused(xs8, ys8)
        fp8 = k8._fused_fp
        assert fp4 and fp8 and fp4 != fp8
        t = make_mlp_trainer()
        t.step(xs4[0], ys4[0])
        assert t._cc_fp and t._cc_fp not in (fp4, fp8)


# -- telemetry: chunk rows + window alignment ---------------------------------

class TestChunkTelemetry:
    def test_accumulator_expands_chunk_rows(self):
        from paddle_tpu.telemetry import Recorder, StepAccumulator
        rec = Recorder()
        acc = StepAccumulator(tag='t', flush_interval=8, recorder=rec)
        acc.observe_chunk(0, 4, step_time_s=0.4, wait_s=0.02,
                          loss=jnp.arange(4.0))
        assert len(acc) == 4    # no flush yet
        acc.observe_chunk(4, 4, step_time_s=0.8,
                          loss=jnp.arange(4.0, 8.0))
        evs = rec.events('steps')
        assert len(evs) == 1
        ev = evs[0]
        # per-STEP rows, not per-chunk: 8 steps, per-step times are
        # the chunk wall divided evenly, losses unstacked in order
        assert ev['n'] == 8
        assert ev['step'] == list(range(8))
        assert ev['loss'] == [float(i) for i in range(8)]
        assert ev['step_time_ms'][:4] == [100.0] * 4
        assert ev['step_time_ms'][4:] == [200.0] * 4
        assert ev['wait_ms'][0] == 20.0
        assert ev['wait_ms'][1] is None

    def test_accumulator_mixed_rows(self):
        from paddle_tpu.telemetry import Recorder, StepAccumulator
        rec = Recorder()
        acc = StepAccumulator(tag='t', flush_interval=64, recorder=rec)
        acc.observe(step=0, step_time_s=0.1, loss=1.5)
        acc.observe_chunk(1, 2, step_time_s=0.2,
                          loss=jnp.asarray([2.5, 3.5]))
        acc.observe(step_time_s=0.1, loss=4.5)  # default step follows
        acc.flush()
        ev = rec.events('steps')[0]
        assert ev['step'] == [0, 1, 2, 3]
        assert ev['loss'] == [1.5, 2.5, 3.5, 4.5]

    def test_profile_window_chunk_aligned(self, monkeypatch, tmp_path):
        from paddle_tpu.telemetry import ProfileSchedule, StepProfiler
        monkeypatch.setattr(jax.profiler, 'start_trace',
                            lambda d: None)
        monkeypatch.setattr(jax.profiler, 'stop_trace', lambda: None)
        sched = ProfileSchedule(every=100, steps=2, start=5, limit=1)
        prof = StepProfiler(sched, base_dir=str(tmp_path), name='t')
        k = 4
        for chunk_lo in range(0, 24, k):
            prof.observe(chunk_lo, span=k)
        assert len(prof.windows) == 1
        win = prof.windows[0]
        # the scheduled start (step 5) lands inside chunk [4..7]; the
        # window opens at the chunk BOUNDARY and covers whole chunks:
        # exact step ids, never a blurred range
        assert win['step_lo'] == 4 and win['step_hi'] == 7
        assert win['steps'] == 4
        assert win['step_lo'] % k == 0

    @pytest.mark.parametrize('v,n,want', [
        (3.0, 1, [3.0]),                    # plain scalar
        (3.0, 4, [3.0] * 4),                # scalar broadcasts
        ([1.0, 2.0], 2, [1.0, 2.0]),        # n-length unstacks
        (np.arange(3.0), 3, [0.0, 1.0, 2.0]),
        (np.arange(6.0).reshape(2, 3), 6,   # any shape, size match
         [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]),
        (np.arange(3.0), 4, [None] * 4),    # size mismatch -> dropped
        ('nan?', 2, [None] * 2),            # unparseable -> dropped
    ])
    def test_expand_scalar_table(self, v, n, want):
        from paddle_tpu.telemetry import StepAccumulator
        assert StepAccumulator._expand_scalar(v, n) == want

    def test_profile_window_span1_unchanged(self, monkeypatch,
                                            tmp_path):
        from paddle_tpu.telemetry import ProfileSchedule, StepProfiler
        monkeypatch.setattr(jax.profiler, 'start_trace',
                            lambda d: None)
        monkeypatch.setattr(jax.profiler, 'stop_trace', lambda: None)
        sched = ProfileSchedule(every=100, steps=2, start=5, limit=1)
        prof = StepProfiler(sched, base_dir=str(tmp_path), name='t')
        for i in range(24):
            prof.observe(i)
        win = prof.windows[0]
        assert win['step_lo'] == 5 and win['step_hi'] == 6


# -- chunk-break lint rule ----------------------------------------------------

class TestChunkBreakRule:
    def _cb_step(self):
        def step(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct(
                    (4,), np.float32), x)
            return y * 2
        return step

    def test_silent_without_fused_intent(self):
        from paddle_tpu import analysis
        rep = analysis.lint(self._cb_step(), jnp.ones(4, jnp.float32),
                            source=False)
        assert not [f for f in rep.findings if f.rule == 'chunk-break']
        # the host-sync rule still fires — chunk-break is additive
        assert [f for f in rep.findings if f.rule == 'host-sync']

    def test_fires_under_fused_intent(self):
        from paddle_tpu import analysis
        from paddle_tpu.analysis import HIGH
        rep = analysis.lint(self._cb_step(), jnp.ones(4, jnp.float32),
                            source=False, fused_steps=8)
        hits = [f for f in rep.findings if f.rule == 'chunk-break']
        assert hits and hits[0].severity == HIGH
        assert 'fused_steps=8' in hits[0].message

    def test_clean_step_stays_clean(self):
        from paddle_tpu import analysis
        rep = analysis.lint(lambda x: x * 2, jnp.ones(4, jnp.float32),
                            source=False, fused_steps=8)
        assert not [f for f in rep.findings if f.rule == 'chunk-break']

    def test_trainer_lint_flags_fused_callback(self):
        import warnings as _w
        rs = np.random.RandomState(0)
        paddle.seed(0)

        class CbLayer(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 4)

            def forward(self, x):
                jax.debug.callback(lambda v: None, x[0, 0])
                return self.fc(x)

        net = CbLayer()
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        ce = nn.CrossEntropyLoss()
        t = ParallelTrainer(net, opt, lambda o, y: ce(o, y),
                            fused_steps=2, lint='warn')
        xs = rs.randn(2, 4, 8).astype('float32')
        ys = rs.randint(0, 4, size=(2, 4, 1)).astype('int64')
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter('always')
            t.step_fused(xs, ys)
        assert any('chunk-break' in str(w.message) for w in rec)


# -- DataLoader device prefetch -----------------------------------------------

class TestDevicePrefetch:
    def _loader(self, **kw):
        from paddle_tpu.io import DataLoader, TensorDataset
        rs = np.random.RandomState(0)
        ds = TensorDataset([rs.randn(16, 4).astype('float32'),
                            rs.randint(0, 2, (16, 1)).astype('int64')])
        return DataLoader(ds, batch_size=4, shuffle=False,
                          to_tensor=False, **kw)

    def test_batches_arrive_on_device(self):
        from paddle_tpu import telemetry
        rec = telemetry.get_recorder()
        before = rec.counters.get('io.device_prefetch.wait_s', 0.0)
        loader = self._loader(num_workers=2, device_prefetch=True)
        batches = list(loader)
        assert len(batches) == 4
        for b in batches:
            assert isinstance(b[0], jax.Array)
            assert isinstance(b[1], jax.Array)
        # the host-wait gauge observed every dequeue
        assert rec.counters.get(
            'io.device_prefetch.wait_s', 0.0) != before or \
            'io.device_prefetch.last_wait_ms' in rec.gauges

    def test_values_unchanged(self):
        plain = [np.asarray(b[0]) for b in
                 self._loader(num_workers=2)]
        staged = [np.asarray(b[0]) for b in
                  self._loader(num_workers=2, device_prefetch=True)]
        for a, b in zip(plain, staged):
            assert np.array_equal(a, b)

    def test_abandoned_iterator_releases_producer(self):
        import threading
        import time as _time
        before = threading.active_count()
        loader = self._loader(num_workers=2, device_prefetch=True)
        it = iter(loader)
        next(it)            # producer running, queue filling
        it.close()          # consumer walks away mid-epoch
        deadline = _time.time() + 5.0
        while threading.active_count() > before and \
                _time.time() < deadline:
            _time.sleep(0.05)
        assert threading.active_count() <= before, \
            'device-prefetch producer thread leaked after close()'

    def test_num_workers0_warns_and_disables(self):
        with pytest.warns(UserWarning, match='device_prefetch'):
            loader = self._loader(num_workers=0, device_prefetch=True)
        assert loader.device_prefetch is False
        batches = list(loader)
        assert len(batches) == 4
        assert isinstance(batches[0][0], np.ndarray)


# -- precompile: declared fused modules ---------------------------------------

class TestPrecompileFused:
    def test_fused_target_entry(self, tmp_path, monkeypatch):
        import sys
        sys.modules.pop('tools.precompile', None)
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        monkeypatch.syspath_prepend(os.path.join(repo, 'tools'))
        import precompile as pc
        from paddle_tpu.core import compile_cache as cc
        cache = tmp_path / 'ccache'
        monkeypatch.setenv(cc.ENV_VAR, str(cache))
        cc.reset_stats()
        run_dir = tmp_path / 'run'
        rc = pc.main([str(run_dir), '--targets', 'lenet',
                      '--fused-steps', '2', '--json'])
        assert rc == 0
        doc = cc.read_precompile_manifest(str(run_dir))
        descs = [e['description'] for e in doc['entries']]
        assert any('fused x2' in d for d in descs)
        assert any('fused' not in d for d in descs)
        assert doc['fused_steps'] == [2]
