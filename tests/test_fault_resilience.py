"""Fault-tolerant training runtime (paddle_tpu.resilience).

Crash-recovery fault injection: torn checkpoint directories (truncated
shard / dropped manifest), SIGKILL between save and commit, SIGTERM
preemption with a final graceful checkpoint, and NaN skip-then-rollback
in both hapi.Model.fit and ParallelTrainer.  These are the paths the
elastic supervisor's restart loop depends on — they stay tier-1
(`faultinject` marker, deliberately not `slow`).

NOTE this file must sort alphabetically before test_host_embedding.py:
the seed's tier-1 run aborts there (XLA compiler crash) and later
files never execute.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.checkpoint import (
    CheckpointManager, save_sharded)
from paddle_tpu.resilience import (
    MANIFEST_NAME, write_manifest, verify_manifest, is_committed,
    retry, NanSentinel, GracefulShutdown, PREEMPTED_EXIT_CODE)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'elastic_worker.py')


def _tree(offset=0.0):
    return {'w': jnp.arange(16.0).reshape(4, 4) + offset,
            'step': jnp.asarray(int(offset))}


def _truncate_largest_payload(step_dir):
    """Damage the checkpoint the way a torn write does: truncate the
    biggest non-manifest file."""
    victim, size = None, -1
    for root, _, files in os.walk(step_dir):
        for f in files:
            if f == MANIFEST_NAME:
                continue
            p = os.path.join(root, f)
            if os.path.getsize(p) > size:
                victim, size = p, os.path.getsize(p)
    assert victim is not None
    with open(victim, 'r+b') as f:
        f.truncate(max(0, size // 2))
    return victim


# ---------------------------------------------------------------- retry --
class TestRetry:
    def test_recovers_after_transient_failures(self):
        calls = []

        @retry(retries=3, backoff=0.01, sleep=lambda d: None)
        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError('transient')
            return 'done'

        assert flaky() == 'done'
        assert len(calls) == 3

    def test_exhausts_and_reraises(self):
        @retry(retries=2, backoff=0.01, sleep=lambda d: None)
        def broken():
            raise OSError('permanent')

        with pytest.raises(OSError, match='permanent'):
            broken()

    def test_non_matching_exception_propagates_immediately(self):
        calls = []

        @retry(retries=5, retry_on=(OSError,), sleep=lambda d: None)
        def wrong_kind():
            calls.append(1)
            raise ValueError('not retriable')

        with pytest.raises(ValueError):
            wrong_kind()
        assert len(calls) == 1

    def test_backoff_grows_and_caps(self):
        delays = []

        @retry(retries=4, backoff=0.1, max_backoff=0.25, jitter=False,
               sleep=delays.append)
        def always():
            raise OSError('x')

        with pytest.raises(OSError):
            always()
        assert delays == [0.1, 0.2, 0.25, 0.25]


# ------------------------------------------------------------- sentinel --
class TestNanSentinel:
    def test_skip_then_rollback_then_reset(self):
        s = NanSentinel(patience=3)
        assert s.observe(loss=1.0) == 'ok'
        assert s.observe(loss=float('nan')) == 'skip'
        assert s.observe(loss=float('inf')) == 'skip'
        assert s.observe(loss=float('nan')) == 'rollback'
        # counter reset: the restored run gets fresh strikes
        assert s.strikes == 0
        assert s.observe(loss=0.5) == 'ok'

    def test_finite_step_resets_strikes(self):
        s = NanSentinel(patience=2)
        assert s.observe(loss=float('nan')) == 'skip'
        assert s.observe(loss=1.0) == 'ok'
        assert s.observe(loss=float('nan')) == 'skip'   # not rollback

    def test_grad_norm_counts(self):
        s = NanSentinel(patience=1)
        assert s.observe(loss=1.0, grad_norm=float('inf')) == 'rollback'

    def test_fatal_after_rollback_budget(self):
        s = NanSentinel(patience=1, max_rollbacks=1)
        assert s.observe(finite=False) == 'rollback'
        with pytest.raises(FloatingPointError, match='diverged'):
            s.observe(finite=False)


# ------------------------------------------------------------- shutdown --
class TestGracefulShutdown:
    def test_request_and_exit_code(self):
        gs = GracefulShutdown()
        assert not gs.requested()
        gs.request()
        assert gs.requested()
        final = []
        with pytest.raises(SystemExit) as ei:
            gs.exit(final=lambda: final.append(1))
        assert ei.value.code == PREEMPTED_EXIT_CODE
        assert final == [1]

    def test_sigterm_latches_instead_of_killing(self):
        with GracefulShutdown(signals=(signal.SIGTERM,)) as gs:
            os.kill(os.getpid(), signal.SIGTERM)
            # handler ran synchronously in this (main) thread
            assert gs.requested()
            assert gs.signum == signal.SIGTERM


# ---------------------------------------------------- commit manifests --
@pytest.mark.faultinject
class TestManifest:
    def test_roundtrip_verifies(self, tmp_path):
        h = save_sharded(_tree(), str(tmp_path / 'ck'),
                         async_save=False, step=7)
        assert h.committed
        ok, errors = verify_manifest(str(tmp_path / 'ck'))
        assert ok, errors
        assert is_committed(str(tmp_path / 'ck'))

    def test_detects_truncation(self, tmp_path):
        save_sharded(_tree(), str(tmp_path / 'ck'), async_save=False)
        _truncate_largest_payload(str(tmp_path / 'ck'))
        ok, errors = verify_manifest(str(tmp_path / 'ck'))
        assert not ok
        assert any('size' in e or 'mismatch' in e for e in errors)

    def test_detects_missing_file(self, tmp_path):
        save_sharded(_tree(), str(tmp_path / 'ck'), async_save=False)
        victim = _truncate_largest_payload(str(tmp_path / 'ck'))
        os.remove(victim)
        ok, errors = verify_manifest(str(tmp_path / 'ck'))
        assert not ok
        assert any('missing' in e for e in errors)

    def test_missing_manifest_is_uncommitted(self, tmp_path):
        save_sharded(_tree(), str(tmp_path / 'ck'), async_save=False,
                     commit=False)
        assert not is_committed(str(tmp_path / 'ck'))
        ok, errors = verify_manifest(str(tmp_path / 'ck'))
        assert not ok

    def test_atomic_replace_keeps_previous_manifest(self, tmp_path):
        d = str(tmp_path / 'ck')
        save_sharded(_tree(), d, async_save=False, step=1)
        first = open(os.path.join(d, MANIFEST_NAME)).read()
        write_manifest(d, step=2)
        second = open(os.path.join(d, MANIFEST_NAME)).read()
        assert json.loads(second)['step'] == 2
        assert json.loads(first)['step'] == 1


# ------------------------------------------- torn-checkpoint recovery --
@pytest.mark.faultinject
class TestTornCheckpointRecovery:
    def test_save_handle_wait_is_idempotent(self, tmp_path):
        h = save_sharded(_tree(), str(tmp_path / 'ck'), async_save=True)
        h.wait()
        h.wait()   # second wait() used to re-enter a closed checkpointer
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        mgr.save(_tree(1), 1)
        mgr.wait()
        mgr.wait()
        assert mgr.latest_step() == 1

    def test_uncommitted_dir_invisible_to_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / 'run'), async_save=False)
        mgr.save(_tree(1), 1)
        # "SIGKILL between save and commit": full data, no manifest
        save_sharded(_tree(2), os.path.join(str(tmp_path / 'run'),
                                            'step_2'),
                     async_save=False, commit=False)
        assert mgr.latest_step() == 1
        restored, got = mgr.restore(_tree())
        assert got == 1
        np.testing.assert_array_equal(np.asarray(restored['w']),
                                      np.asarray(_tree(1)['w']))

    def test_truncated_shard_falls_back_and_quarantines(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        mgr.save(_tree(2), 2)
        _truncate_largest_payload(os.path.join(d, 'step_2'))
        with pytest.warns(RuntimeWarning, match='failed verification'):
            restored, got = mgr.restore(_tree())
        assert got == 1
        np.testing.assert_array_equal(np.asarray(restored['w']),
                                      np.asarray(_tree(1)['w']))
        # torn dir preserved under quarantine, never selected again
        assert any('.torn-' in f for f in os.listdir(d))
        assert mgr.latest_step() == 1

    def test_dropped_manifest_falls_back(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        mgr.save(_tree(2), 2)
        os.remove(os.path.join(d, 'step_2', MANIFEST_NAME))
        assert mgr.latest_step() == 1
        restored, got = mgr.restore(_tree())
        assert got == 1

    def test_explicit_step_request_falls_back_too(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        mgr.save(_tree(2), 2)
        os.remove(os.path.join(d, 'step_2', MANIFEST_NAME))
        with pytest.warns(RuntimeWarning):
            restored, got = mgr.restore(_tree(), step=2)
        assert got == 1
        # an UNCOMMITTED dir is never quarantined: it may be another
        # process's in-flight save (only committed-but-corrupt dirs,
        # which no one can still be writing, get moved aside)
        assert os.path.isdir(os.path.join(d, 'step_2'))
        assert not any('.torn-' in f for f in os.listdir(d))

    def test_wrong_template_fails_fast_with_named_leaves(self,
                                                         tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        wrong = {'w': jnp.zeros((2, 2)), 'step': jnp.asarray(0)}
        with pytest.raises(ValueError, match='does not match'):
            mgr.restore(wrong)

    def test_no_committed_checkpoint_returns_none(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / 'empty'))
        restored, got = mgr.restore(_tree())
        assert restored is None and got == -1

    def test_python_scalar_leaves_roundtrip(self, tmp_path):
        """Manifest leaf_spec must abstractify consistently: a python
        int leaf records the same dtype at save and restore time."""
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save({'w': jnp.arange(4.0), 'epoch': 3}, 1)
        restored, got = mgr.restore({'w': jnp.zeros(4), 'epoch': 0})
        assert got == 1
        assert int(np.asarray(restored['epoch'])) == 3

    def test_legacy_uncommitted_dirs_warn_and_adopt(self, tmp_path):
        """Pre-manifest checkpoints are invisible but NOT silent:
        restore warns, and check_ckpt --adopt migrates them."""
        d = str(tmp_path / 'run')
        # legacy-era checkpoint: valid orbax data, no manifest
        save_sharded(_tree(5), os.path.join(d, 'step_5'),
                     async_save=False, commit=False)
        mgr = CheckpointManager(d)
        with pytest.warns(RuntimeWarning, match='no commit manifest'):
            restored, got = mgr.restore(_tree())
        assert got == -1
        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, 'tools',
                                          'check_ckpt.py'), d,
             '--adopt'], capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        assert mgr.latest_step() == 5
        restored, got = mgr.restore(_tree())
        assert got == 5

    def test_prune_spares_uncommitted_dirs(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, keep=2, async_save=False)
        # an uncommitted dir (in-flight save from a sibling process)
        save_sharded(_tree(0), os.path.join(d, 'step_0'),
                     async_save=False, commit=False)
        for s in (1, 2, 3, 4):
            mgr.save(_tree(s), s)
        assert mgr._steps(committed=True) == [3, 4]
        assert os.path.isdir(os.path.join(d, 'step_0'))   # untouched

    def test_sigkill_between_save_and_commit_subprocess(self, tmp_path):
        """A real SIGKILL after the save barrier but before the commit
        manifest: the reader must select the previous committed step."""
        d = str(tmp_path / 'run')
        script = textwrap.dedent(f'''
            import os, signal, sys
            sys.path.insert(0, {_REPO!r})
            os.environ['JAX_PLATFORMS'] = 'cpu'
            import jax.numpy as jnp
            from paddle_tpu.distributed.checkpoint import (
                CheckpointManager, save_sharded)
            tree = lambda o: {{'w': jnp.arange(16.0).reshape(4, 4) + o,
                               'step': jnp.asarray(int(o))}}
            mgr = CheckpointManager({d!r}, async_save=False)
            mgr.save(tree(1), 1)
            save_sharded(tree(2), os.path.join({d!r}, 'step_2'),
                         async_save=False, commit=False)
            os.kill(os.getpid(), signal.SIGKILL)   # dies pre-commit
        ''')
        p = subprocess.run([sys.executable, '-c', script],
                           capture_output=True, text=True, timeout=180)
        assert p.returncode == -signal.SIGKILL, p.stderr
        mgr = CheckpointManager(d)
        assert mgr.latest_step() == 1
        restored, got = mgr.restore(_tree())
        assert got == 1
        np.testing.assert_array_equal(np.asarray(restored['w']),
                                      np.asarray(_tree(1)['w']))


# ------------------------------------------------- preemption handling --
def _env(extra=None):
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'
    env['PYTHONPATH'] = _REPO + os.pathsep + env.get('PYTHONPATH', '')
    if extra:
        env.update(extra)
    return env


@pytest.mark.faultinject
class TestPreemption:
    def test_preempted_exit_restarts_without_burning_budget(self):
        """Unit-level: PREEMPTED_EXIT_CODE restarts for free even with
        max_restarts=0; a plain failure would have ended the job."""
        from paddle_tpu.distributed import elastic
        script = (
            'import os, sys;'
            'sys.exit(0 if os.environ.get("PADDLE_ELASTIC_'
            f'PREEMPT_COUNT", "0") != "0" else {PREEMPTED_EXIT_CODE})')
        events = []
        procs = elastic.start_local_trainers(
            [[sys.executable, '-c', script]])
        rc = elastic.watch_local_trainers(
            procs, max_restarts=0, poll=0.05, min_preempt_uptime=0.0,
            on_event=lambda k, t: events.append(k))
        assert rc == 0
        assert events == ['preempt', 'restart']
        assert procs[0].restarts == 0
        assert procs[0].preemptions == 1

    def test_instant_preempt_loop_counts_as_failure(self):
        """A worker that exits PREEMPTED within min_preempt_uptime of
        spawning is a preemption LOOP, not a preemption — it burns the
        failure budget instead of respawning forever."""
        from paddle_tpu.distributed import elastic
        procs = elastic.start_local_trainers(
            [[sys.executable, '-c',
              f'import sys; sys.exit({PREEMPTED_EXIT_CODE})']])
        rc = elastic.watch_local_trainers(
            procs, max_restarts=0, poll=0.05, min_preempt_uptime=3600)
        assert rc == PREEMPTED_EXIT_CODE
        assert procs[0].preemptions == 0

    def test_deleted_heartbeat_counts_as_stale(self, tmp_path):
        """Satellite fix: a heartbeat file deleted mid-run used to
        silently disable hang detection."""
        from paddle_tpu.distributed import elastic
        hb = str(tmp_path / 'hb')
        events = []
        procs = elastic.start_local_trainers(
            [[sys.executable, '-c', 'import time; time.sleep(300)']])

        def deleter():
            time.sleep(0.2)
            try:
                os.remove(hb)
            except OSError:
                pass

        threading.Thread(target=deleter, daemon=True).start()
        rc = elastic.watch_local_trainers(
            procs, max_restarts=0, poll=0.05, heartbeat_file=hb,
            heartbeat_timeout=5.0,
            on_event=lambda k, t: events.append(k))
        assert 'hang' in events
        assert rc != 0

    @staticmethod
    def _reference_state():
        """The elastic worker's training, replayed in-process (no acp,
        no subprocess): deterministic seed + data ⇒ identical final
        state to an uninterrupted worker run."""
        paddle.seed(42)
        model = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        rs = np.random.RandomState(0)
        xs = rs.rand(20, 4).astype('float32')
        ys = (xs.sum(axis=1, keepdims=True) * 0.5).astype('float32')
        loss = None
        for step in range(12):
            x = paddle.to_tensor(xs[step % 5 * 4:(step % 5) * 4 + 4])
            y = paddle.to_tensor(ys[step % 5 * 4:(step % 5) * 4 + 4])
            loss = nn.functional.mse_loss(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
        return {'final_loss': float(np.asarray(loss.value)),
                'weight': np.asarray(
                    model.weight.value).ravel().tolist(),
                'bias': np.asarray(model.bias.value).ravel().tolist()}

    def test_sigterm_preemption_checkpoints_and_resumes(self, tmp_path):
        """End to end: the worker SIGTERMs itself mid-training; the
        auto-checkpoint range saves a final snapshot at the step
        boundary and exits PREEMPTED_EXIT_CODE; the supervisor (with
        max_restarts=0 — ZERO failure budget) restarts it for free and
        the job finishes with the same state as an uninterrupted run."""
        ref = self._reference_state()

        out_json = str(tmp_path / 'out.json')
        p = subprocess.run(
            [sys.executable, '-m', 'paddle_tpu.distributed.launch',
             '--elastic', '0', _WORKER, out_json,
             str(tmp_path / 'ckpt_term')],
            env=_env({'TERM_AT_STEP': '6',
                      # the whole worker lives only a few seconds, so
                      # disable the preemption-loop heuristic that
                      # would misread its graceful exit as a storm
                      'PADDLE_TPU_MIN_PREEMPT_UPTIME': '0'}),
            cwd=_REPO,
            capture_output=True, text=True, timeout=240)
        assert p.returncode == 0, p.stdout + p.stderr
        got = json.load(open(out_json))
        # the finishing incarnation came from a FREE (preempt) restart:
        # the failure budget (0) was never touched
        assert got['preemptions'] == 1
        assert got['incarnation'] == 0
        np.testing.assert_allclose(got['weight'], ref['weight'],
                                   rtol=1e-6)
        np.testing.assert_allclose(got['bias'], ref['bias'], rtol=1e-6)
        np.testing.assert_allclose(got['final_loss'],
                                   ref['final_loss'], rtol=1e-6)


# ------------------------------------------------- NaN skip + rollback --
@pytest.mark.faultinject
class TestNanRollback:
    def _model(self):
        paddle.seed(0)
        net = nn.Linear(4, 2)
        model = paddle.hapi.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        model.prepare(optimizer=opt, loss=nn.MSELoss())
        return model

    def test_train_batch_skips_nonfinite_update(self):
        model = self._model()
        rs = np.random.RandomState(0)
        x = rs.randn(8, 4).astype('float32')
        y = rs.randn(8, 2).astype('float32')
        model.train_batch(x, y)
        w_good = np.asarray(model._fstate['params']['weight'])
        step_good = model._fstate['step']

        xbad = x.copy()
        xbad[0, 0] = np.nan
        loss, logs = model.train_batch(xbad, y)
        assert not model._last_step_ok
        assert logs == []          # a skipped step feeds no metrics
        np.testing.assert_array_equal(
            w_good, np.asarray(model._fstate['params']['weight']))
        assert model._fstate['step'] == step_good
        # training continues cleanly after the skip
        model.train_batch(x, y)
        assert model._last_step_ok

    def test_fit_nan_triggers_skip_then_rollback(self):
        """Acceptance gate: injected NaN loss in Model.fit causes
        skip-then-rollback instead of propagating into the params."""
        from paddle_tpu.hapi.callbacks import NanGuard
        rs = np.random.RandomState(0)
        x = rs.randn(8, 4).astype('float32')
        y = rs.randn(8, 2).astype('float32')
        xbad = x.copy()
        xbad[0, 0] = np.nan

        class Data:
            def __init__(self):
                self.epoch = 0

            def __iter__(self):
                bad = self.epoch >= 1
                self.epoch += 1
                for i in range(4):
                    yield [xbad if (bad and i >= 1) else x, y]

            def __len__(self):
                return 4

        model = self._model()
        guard = NanGuard(patience=2, max_rollbacks=5, verbose=0)
        model.fit(Data(), epochs=2, verbose=0, callbacks=[guard])
        assert guard.sentinel.total_skipped >= 2
        assert guard.sentinel.rollbacks >= 1
        for p in model.network.parameters():
            assert np.isfinite(np.asarray(p.value)).all()

    def test_fit_sigterm_preemption_saves_final_and_exits(self,
                                                          tmp_path):
        """A SIGTERM latched during fit stops at the step boundary,
        ModelCheckpoint writes the final checkpoint, and fit exits
        PREEMPTED_EXIT_CODE (the code the supervisor restarts for
        free)."""
        from paddle_tpu.resilience import shutdown as sd
        from paddle_tpu.hapi.callbacks import Callback

        class PreemptAt(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 1:
                    sd.install_shutdown().request(signal.SIGTERM)

        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 4
        model = self._model()
        save_dir = str(tmp_path / 'ckpt')
        try:
            with pytest.raises(SystemExit) as ei:
                model.fit(data, epochs=3, verbose=0, save_dir=save_dir,
                          callbacks=[PreemptAt()])
            assert ei.value.code == PREEMPTED_EXIT_CODE
            # the final checkpoint landed before the exit
            assert os.path.exists(
                os.path.join(save_dir, 'final.pdparams'))
        finally:
            sd.clear_shutdown()

    def test_fit_sigint_stop_returns_and_clears(self):
        """A latched SIGINT (user Ctrl-C) stops training but hands
        control back (no exit) and un-latches for the next fit."""
        from paddle_tpu.resilience import shutdown as sd
        from paddle_tpu.hapi.callbacks import Callback

        class StopAt(Callback):
            def on_train_batch_end(self, step, logs=None):
                sd.install_shutdown().request(signal.SIGINT)

        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 4
        model = self._model()
        try:
            model.fit(data, epochs=3, verbose=0, callbacks=[StopAt()])
            assert not sd.shutdown_requested()   # cleared on return
            model.fit(data, epochs=1, verbose=0)  # runs fine again
        finally:
            sd.clear_shutdown()

    def test_fit_programmatic_request_exits_preempted(self):
        """request() with no signal (cluster agent learned of the
        preemption out-of-band) is a preemption, not a user stop:
        fit exits PREEMPTED_EXIT_CODE like the SIGTERM path."""
        from paddle_tpu.resilience import shutdown as sd
        from paddle_tpu.hapi.callbacks import Callback

        class StopAt(Callback):
            def on_train_batch_end(self, step, logs=None):
                sd.install_shutdown().request()

        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 4
        model = self._model()
        try:
            with pytest.raises(SystemExit) as ei:
                model.fit(data, epochs=3, verbose=0,
                          callbacks=[StopAt()])
            assert ei.value.code == PREEMPTED_EXIT_CODE
        finally:
            sd.clear_shutdown()

    def test_fit_diverging_run_raises_after_rollback_budget(self):
        from paddle_tpu.hapi.callbacks import NanGuard
        x = np.full((8, 4), np.nan, dtype='float32')
        y = np.zeros((8, 2), dtype='float32')
        data = [[x, y]] * 8
        model = self._model()
        guard = NanGuard(patience=1, max_rollbacks=1, verbose=0)
        with pytest.raises(FloatingPointError, match='diverged'):
            model.fit(data, epochs=1, verbose=0, callbacks=[guard])


# --------------------------------------------------- check_ckpt CLI ----
@pytest.mark.faultinject
class TestCheckCkptCLI:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(_REPO, 'tools',
                                          'check_ckpt.py'), *args],
            capture_output=True, text=True, timeout=120)

    def test_reports_latest_committed(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        mgr.save(_tree(2), 2)
        os.remove(os.path.join(d, 'step_2', MANIFEST_NAME))
        p = self._run(d)
        assert p.returncode == 0, p.stderr
        assert 'UNCOMMITTED' in p.stdout
        assert p.stdout.strip().endswith('1')
        p = self._run(d, '--quiet')
        assert p.stdout.strip() == '1'

    def test_detects_corruption(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        _truncate_largest_payload(os.path.join(d, 'step_1'))
        p = self._run(d)
        assert p.returncode == 1
        assert 'CORRUPT' in p.stdout
        assert p.stdout.strip().endswith('-1')

    def test_empty_dir_exits_nonzero(self, tmp_path):
        p = self._run(str(tmp_path))
        assert p.returncode == 1


# ------------------------------------------ snapshot corruption (acp) --
@pytest.mark.faultinject
class TestAutoCheckpointCorruption:
    def test_corrupt_snapshot_starts_over_instead_of_crashing(
            self, tmp_path):
        from paddle_tpu.incubate.checkpoint import auto_checkpoint as acp
        paddle.seed(0)
        model = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        acp.configure(checkpoint_dir=str(tmp_path), model=model,
                      optimizer=opt, save_checkpoint_inter=0)
        assert list(acp.train_epoch_range(3)) == [0, 1, 2]
        snap = os.path.join(str(tmp_path), 'acp_snapshot')
        with open(snap, 'wb') as f:
            f.write(b'\x80\x04 definitely not a pickle')
        acp.configure(checkpoint_dir=str(tmp_path), model=model,
                      optimizer=opt, save_checkpoint_inter=0)
        with pytest.warns(RuntimeWarning, match='unreadable'):
            seen = list(acp.train_epoch_range(3))
        assert seen == [0, 1, 2]   # restarted from scratch, no crash
