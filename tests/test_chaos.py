"""Chaos-proof multi-host resilience (paddle_tpu.resilience.chaos).

The deterministic fault-injection engine and the three runtime
hardening changes it proves: cross-host TWO-PHASE checkpoint commit
(intent/ack files + process-0 finalize, kill-between-the-phases
safety, half-committed quarantine), ELASTIC RESHAPE restore (a dp=8
checkpoint resumed exactly on dp=4 / dp=2 layouts), and nan_guard
under 1F1B PIPELINE parallelism (per-microbatch finite reduction,
skip-then-rollback).  Plus the satellites: retry(deadline=) + retry
telemetry, elastic crash-restart backoff, check_ckpt --deep failure
classes, and the chaos_run driver's invariant gate.

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
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.distributed import env as dist_env, fleet
from paddle_tpu.distributed.checkpoint import (
    CheckpointManager, save_sharded)
from paddle_tpu.resilience import (
    manifest as M, retry, FaultPlan, Fault, ChaosEngine,
    check_invariants, CommitBarrierTimeout, PREEMPTED_EXIT_CODE)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_check_ckpt_mod = None


def _check_ckpt():
    """tools/check_ckpt loaded in-process (no package __init__): the
    CLI-through-subprocess path is already covered by
    test_fault_resilience; here only main()'s classification/exit
    codes are under test, and skipping ~6 jax-importing subprocesses
    keeps this file inside the tier-1 time budget."""
    global _check_ckpt_mod
    if _check_ckpt_mod is None:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            'check_ckpt', os.path.join(_REPO, 'tools', 'check_ckpt.py'))
        _check_ckpt_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_check_ckpt_mod)
    return _check_ckpt_mod


def _tree(offset=0.0):
    return {'w': jnp.arange(16.0).reshape(4, 4) + offset,
            'step': jnp.asarray(int(offset))}


def _events(kind):
    return list(telemetry.events(kind))


# ------------------------------------------------------- FaultPlan engine --
class TestFaultPlan:
    def test_json_roundtrip(self):
        plan = FaultPlan(seed=11, name='p', faults=[
            Fault('sigkill', at_step=5),
            Fault('io_error', prob=0.3, path='commit',
                  errno_name='ENOSPC'),
        ])
        back = FaultPlan.from_json(plan.to_json())
        assert back.seed == 11 and back.name == 'p'
        assert [f.kind for f in back.faults] == ['sigkill', 'io_error']
        assert back.faults[1].prob == 0.3
        assert back.faults[1].errno_name == 'ENOSPC'

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match='unknown fault kind'):
            Fault('meteor_strike')

    def test_same_seed_replays_identical_sequence(self, tmp_path,
                                                  chaos):
        """The replayability contract: the SAME FaultPlan(seed=...)
        applied to the SAME scenario injects the IDENTICAL
        fault-event sequence twice."""
        def scenario(engine):
            for i in range(20):
                try:
                    M.atomic_write(str(tmp_path / f'f{i}'),
                                   lambda f: f.write('x'))
                except OSError:
                    pass
            return engine.sequence()

        plan = {'seed': 42, 'faults': [
            Fault('io_error', prob=0.5, path=str(tmp_path))]}
        first = scenario(chaos(dict(plan)))
        second = scenario(chaos(
            {'seed': 42,
             'faults': [Fault('io_error', prob=0.5,
                              path=str(tmp_path))]}))
        assert first == second
        assert first, 'seeded plan injected nothing in 20 tries'

    def test_different_seed_differs(self, tmp_path, chaos):
        def scenario(engine):
            for i in range(30):
                try:
                    M.atomic_write(str(tmp_path / f'g{i}'),
                                   lambda f: f.write('x'))
                except OSError:
                    pass
            return [e['seq'] for e in engine.sequence()]

        a = scenario(chaos({'seed': 1, 'faults': [
            Fault('io_error', prob=0.5, path=str(tmp_path))]}))
        # same scenario under another seed: the injected subset of the
        # 30 opportunities must differ (probability 2^-30 otherwise)
        tmp2 = tmp_path
        eng_b = chaos({'seed': 2, 'faults': [
            Fault('io_error', prob=0.5, path=str(tmp2))]})
        hits_b = []
        for i in range(30):
            try:
                M.atomic_write(str(tmp2 / f'g{i}'),
                               lambda f: f.write('x'))
                hits_b.append(False)
            except OSError:
                hits_b.append(True)
        assert a != [i for i, h in enumerate(hits_b) if h] or \
            len(a) != sum(hits_b)


# ------------------------------------------------------------- file seam --
@pytest.mark.faultinject
class TestFileSeam:
    def test_io_error_carries_errno(self, tmp_path, chaos):
        chaos({'seed': 0, 'faults': [
            Fault('io_error', prob=1.0, errno_name='ENOSPC')]})
        with pytest.raises(OSError) as ei:
            M.atomic_write(str(tmp_path / 'x'), lambda f: f.write('d'))
        import errno
        assert ei.value.errno == errno.ENOSPC

    def test_fault_emits_telemetry_event(self, tmp_path, chaos):
        before = len(_events('fault_injected'))
        chaos({'seed': 0, 'faults': [Fault('io_error', prob=1.0)]})
        with pytest.raises(OSError):
            M.atomic_write(str(tmp_path / 'x'), lambda f: f.write('d'))
        evs = _events('fault_injected')
        assert len(evs) == before + 1
        assert evs[-1]['fault'] == 'io_error'

    def test_slow_io_delays(self, tmp_path, chaos):
        chaos({'seed': 0, 'faults': [
            Fault('slow_io', prob=1.0, delay_s=0.15)]})
        t0 = time.monotonic()
        M.atomic_write(str(tmp_path / 'x'), lambda f: f.write('d'))
        assert time.monotonic() - t0 >= 0.14
        assert open(tmp_path / 'x').read() == 'd'   # write still lands

    def test_torn_write_defeats_commit(self, tmp_path, chaos):
        """A torn manifest write (half the bytes, no atomic rename)
        must read back as UNCOMMITTED — the exact reader behaviour the
        manifest protocol promises for torn saves."""
        d = str(tmp_path / 'ck')
        save_sharded(_tree(), d, async_save=False, commit=False)
        chaos({'seed': 0, 'faults': [
            Fault('torn_write', path=M.MANIFEST_NAME)]})
        M.write_manifest(d, step=1)
        assert M.read_manifest(d) is None
        assert not M.is_committed(d)

    def test_seam_unpatches_on_exit(self, tmp_path):
        plan = FaultPlan(seed=0, faults=[Fault('io_error', prob=1.0)])
        with ChaosEngine(plan):
            with pytest.raises(OSError):
                M.atomic_write(str(tmp_path / 'x'),
                               lambda f: f.write('d'))
        M.atomic_write(str(tmp_path / 'x'), lambda f: f.write('ok'))
        assert open(tmp_path / 'x').read() == 'ok'


# ----------------------------------------------------- two-phase commit --
@pytest.mark.faultinject
class TestTwoPhaseCommit:
    def test_forced_two_phase_single_host_commits(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / 'run'),
                                async_save=False, two_phase=True,
                                num_hosts=1, barrier_timeout=10)
        mgr.save(_tree(1), 1)
        p = os.path.join(str(tmp_path / 'run'), 'step_1')
        doc = M.read_manifest(p)
        assert doc is not None and doc['hosts'] == 1
        assert os.path.isfile(os.path.join(
            p, M.TWO_PHASE_DIR, 'intent.r0'))
        ok, errors = M.verify_manifest(p)
        assert ok, errors
        restored, got = mgr.restore(_tree())
        assert got == 1

    def test_simulated_hosts_merge_with_attribution(self, tmp_path):
        """Three simulated hosts ack disjoint shard sets; the merged
        manifest tags every file with its owner and verifies."""
        d = str(tmp_path / 'ck')
        save_sharded(_tree(2), d, async_save=False, commit=False)
        rels = [rel for rel, _ in sorted(
            (r, p) for r, p in _walk(d))]
        thirds = [rels[i::3] for i in range(3)]
        for h in range(3):
            M.write_intent(d, h, step=2, files=thirds[h])
        doc = M.finalize_two_phase(d, 3, step=2, timeout=5)
        assert doc['hosts'] == 3
        owners = {meta['host'] for meta in doc['files'].values()}
        assert owners == {0, 1, 2}
        ok, errors = M.verify_manifest(d)
        assert ok, errors

    def test_missing_ack_times_out_not_commits(self, tmp_path):
        d = str(tmp_path / 'ck')
        save_sharded(_tree(3), d, async_save=False, commit=False)
        M.write_intent(d, 0, step=3, files=())
        t0 = time.monotonic()
        with pytest.raises(CommitBarrierTimeout) as ei:
            M.finalize_two_phase(d, 3, step=3, timeout=0.5)
        assert ei.value.missing == [1, 2]
        # the deadline is a CAP: the barrier retries until a further
        # sleep would cross it, so elapsed ∈ (something, timeout]
        assert 0.2 <= time.monotonic() - t0 <= 2.0
        assert not M.is_committed(d)       # barrier timeout ≠ commit

    def test_barrier_emits_span_and_finalize_event(self, tmp_path):
        d = str(tmp_path / 'ck')
        save_sharded(_tree(4), d, async_save=False, commit=False)
        M.write_intent(d, 0, step=4)
        before_f = len(_events('commit_finalize'))
        before_i = len(_events('commit_intent'))
        telemetry.enable()      # spans keep records only when enabled
        try:
            M.finalize_two_phase(d, 1, step=4, timeout=5)
        finally:
            telemetry.disable()
        assert len(_events('commit_finalize')) == before_f + 1
        assert len(_events('commit_intent')) == before_i
        spans = [e for e in _events('span')
                 if e.get('name') == 'commit_barrier']
        assert spans and spans[-1]['hosts'] == 1
        assert spans[-1]['start'] <= spans[-1]['end']

    def test_sigkill_between_intent_and_finalize(self, tmp_path):
        """THE two-phase crash window: every host acked, the finalizer
        died before the manifest.  restore() must yield the previous
        committed step — and once the acks are stale, quarantine the
        half-committed dir."""
        d = str(tmp_path / 'run')
        script = textwrap.dedent(f'''
            import os, signal, sys
            sys.path.insert(0, {_REPO!r})
            os.environ['JAX_PLATFORMS'] = 'cpu'
            import jax.numpy as jnp
            from paddle_tpu.distributed.checkpoint import (
                CheckpointManager, save_sharded)
            from paddle_tpu.resilience import manifest as M
            tree = lambda o: {{'w': jnp.arange(16.0).reshape(4, 4) + o,
                               'step': jnp.asarray(int(o))}}
            mgr = CheckpointManager({d!r}, async_save=False)
            mgr.save(tree(1), 1)
            p2 = os.path.join({d!r}, 'step_2')
            save_sharded(tree(2), p2, async_save=False, commit=False)
            M.write_intent(p2, 0, step=2)
            M.write_intent(p2, 1, step=2, files=())
            os.kill(os.getpid(), signal.SIGKILL)  # dies pre-finalize
        ''')
        p = subprocess.run([sys.executable, '-c', script],
                           capture_output=True, text=True, timeout=180)
        assert p.returncode == -signal.SIGKILL, p.stderr
        # acks present, no manifest: uncommitted to every reader
        assert M.read_intents(os.path.join(d, 'step_2'))
        assert not M.is_committed(os.path.join(d, 'step_2'))
        mgr = CheckpointManager(d)          # default grace: fresh acks
        assert mgr.latest_step() == 1
        with pytest.warns(RuntimeWarning, match='no commit manifest'):
            restored, got = mgr.restore(_tree(), step=2)
        assert got == 1
        assert os.path.isdir(os.path.join(d, 'step_2'))  # untouched
        # stale acks (grace 0): half-committed, quarantined
        mgr2 = CheckpointManager(d, half_commit_grace=0.0)
        with pytest.warns(RuntimeWarning, match='half-committed'):
            restored, got = mgr2.restore(_tree())
        assert got == 1
        assert not os.path.isdir(os.path.join(d, 'step_2'))
        assert any('.torn-' in f for f in os.listdir(d))
        np.testing.assert_array_equal(np.asarray(restored['w']),
                                      np.asarray(_tree(1)['w']))

    def test_intent_files_never_pollute_manifest(self, tmp_path):
        d = str(tmp_path / 'ck')
        save_sharded(_tree(5), d, async_save=False, commit=False)
        M.write_intent(d, 0, step=5)
        doc = M.finalize_two_phase(d, 1, step=5, timeout=5)
        assert not any(M.TWO_PHASE_DIR in rel for rel in doc['files'])
        # and a plain write_manifest over a 2PC dir skips them too
        doc2 = M.write_manifest(d, step=5)
        assert not any(M.TWO_PHASE_DIR in rel for rel in doc2['files'])


def _walk(d):
    for root, dirs, files in os.walk(d):
        if M.TWO_PHASE_DIR in dirs:
            dirs.remove(M.TWO_PHASE_DIR)
        for f in files:
            if f != M.MANIFEST_NAME:
                p = os.path.join(root, f)
                yield os.path.relpath(p, d), p


# ------------------------------------------------------ retry satellite --
class TestRetryDeadline:
    def test_deadline_caps_total_wall_clock(self):
        sleeps = []

        @retry(retries=100, backoff=10.0, jitter=False,
               sleep=sleeps.append, deadline=0.05)
        def always():
            raise OSError('x')

        with pytest.raises(OSError):
            always()
        # the first retry's 10s sleep would blow the 0.05s deadline:
        # re-raise immediately, zero sleeps
        assert sleeps == []

    def test_deadline_allows_fast_retries(self):
        calls = []

        @retry(retries=5, backoff=0.001, jitter=False,
               sleep=lambda d: None, deadline=30.0)
        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError('t')
            return 'ok'

        assert flaky() == 'ok'

    def test_default_on_retry_emits_telemetry(self):
        before = len(_events('retry'))

        @retry(retries=2, backoff=0.001, sleep=lambda d: None)
        def flaky():
            if len(_events('retry')) - before < 1:
                raise OSError('transient')
            return 'ok'

        assert flaky() == 'ok'
        evs = _events('retry')
        assert len(evs) == before + 1
        assert evs[-1]['fn'] == 'flaky'
        assert 'transient' in evs[-1]['error']

    def test_custom_on_retry_suppresses_default(self):
        seen = []
        before = len(_events('retry'))

        @retry(retries=2, backoff=0.001, sleep=lambda d: None,
               on_retry=lambda e, k: seen.append(k))
        def flaky():
            if not seen:
                raise OSError('t')
            return 'ok'

        assert flaky() == 'ok'
        assert seen == [0]
        assert len(_events('retry')) == before


# ------------------------------------------------ elastic restart backoff --
@pytest.mark.faultinject
class TestElasticBackoff:
    def test_crash_loop_restarts_are_spaced(self):
        """A crash-looping worker used to burn max_restarts in
        milliseconds; with exponential backoff the budget spans real
        time (0.2 + 0.4 = 0.6s minimum here)."""
        from paddle_tpu.distributed import elastic
        events = []
        procs = elastic.start_local_trainers(
            [[sys.executable, '-c', 'import sys; sys.exit(3)']])
        t0 = time.monotonic()
        rc = elastic.watch_local_trainers(
            procs, max_restarts=2, poll=0.02, restart_backoff=0.2,
            restart_backoff_max=5.0,
            on_event=lambda k, t: events.append(k))
        elapsed = time.monotonic() - t0
        assert rc == 3
        assert events.count('backoff') == 2
        assert elapsed >= 0.55, elapsed

    def test_preempted_restarts_skip_backoff(self):
        """Preemption restarts are free AND immediate — the fleet
        already imposed the wait; only crashes back off."""
        from paddle_tpu.distributed import elastic
        script = (
            'import os, sys;'
            'sys.exit(0 if os.environ.get("PADDLE_ELASTIC_'
            f'PREEMPT_COUNT", "0") != "0" else {PREEMPTED_EXIT_CODE})')
        events = []
        procs = elastic.start_local_trainers(
            [[sys.executable, '-c', script]])
        t0 = time.monotonic()
        rc = elastic.watch_local_trainers(
            procs, max_restarts=0, poll=0.02, min_preempt_uptime=0.0,
            restart_backoff=30.0,           # would be visible if hit
            on_event=lambda k, t: events.append(k))
        assert rc == 0
        assert 'backoff' not in events
        assert time.monotonic() - t0 < 20.0


# -------------------------------------------------- elastic reshape restore --
@pytest.mark.faultinject
class TestReshapeRestore:
    @pytest.fixture(autouse=True)
    def _clean_mesh(self):
        yield
        dist_env.set_mesh(None)

    def test_dp8_checkpoint_restores_onto_dp4_and_dp2(self, tmp_path):
        """Acceptance gate: a checkpoint committed under dp=8 restores
        EXACTLY onto dp=4 and dp=2 layouts (a preempted pool resuming
        smaller), and the topology change lands in telemetry."""
        rs = np.random.RandomState(0)
        w = rs.randn(16, 4).astype('float32')
        b = rs.randn(8).astype('float32')
        mesh8 = dist_env.build_mesh([('dp', 8)])
        tree8 = {
            'w': jax.device_put(w, NamedSharding(mesh8, P('dp'))),
            'b': jax.device_put(b, NamedSharding(mesh8, P())),
            'step': jnp.asarray(3)}
        mgr = CheckpointManager(str(tmp_path / 'run'),
                                async_save=False)
        mgr.save(tree8, 3)
        doc = M.read_manifest(str(tmp_path / 'run' / 'step_3'))
        assert doc['mesh'] == {'dp': 8}
        for ndev in (4, 2):
            mesh = Mesh(np.asarray(jax.devices()[:ndev]), ('dp',))
            like = {
                'w': jax.ShapeDtypeStruct(
                    (16, 4), jnp.float32,
                    sharding=NamedSharding(mesh, P('dp'))),
                'b': jax.ShapeDtypeStruct(
                    (8,), jnp.float32,
                    sharding=NamedSharding(mesh, P())),
                'step': jnp.asarray(0)}
            before = len(_events('reshape_restore'))
            # a fresh manager per layout: the restoring pool is a new
            # process in real life
            restored, got = CheckpointManager(
                str(tmp_path / 'run')).restore(like)
            assert got == 3
            np.testing.assert_array_equal(np.asarray(restored['w']), w)
            np.testing.assert_array_equal(np.asarray(restored['b']), b)
            assert restored['w'].sharding.mesh.shape == {'dp': ndev}
            evs = _events('reshape_restore')
            assert len(evs) == before + 1
            assert evs[-1]['saved_mesh'] == {'dp': 8}
            assert evs[-1]['mesh'] == {'dp': ndev}

    def test_trainer_restores_onto_smaller_mesh(self, tmp_path):
        """ParallelTrainer wiring: state saved by a dp=4 x mp=2
        trainer restores into a dp=2 x mp=2 trainer (half the pool)
        with identical parameter values."""
        rs = np.random.RandomState(0)
        x = rs.randn(8, 16).astype('float32')
        y = rs.randn(8, 8).astype('float32')

        def make(dp):
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs['dp_degree'] = dp
            strategy.hybrid_configs['mp_degree'] = 2
            fleet.init(is_collective=True, strategy=strategy)
            paddle.seed(0)
            model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(),
                                  nn.Linear(32, 8))
            mse = nn.MSELoss()
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=model.parameters())
            from paddle_tpu.parallel import ParallelTrainer
            return ParallelTrainer(model, opt, lambda o, t: mse(o, t),
                                   strategy=strategy)

        tr = make(dp=4)
        for _ in range(2):
            tr.step(x, y)
        tr.save_checkpoint(str(tmp_path / 'run'), async_save=False)
        saved = {n: np.asarray(v) for n, v in tr.params.items()}

        dist_env.set_mesh(None)
        tr2 = make(dp=2)
        got = tr2.restore_checkpoint(str(tmp_path / 'run'))
        assert got == 2, got
        assert tr2._step_no == 2
        for n, v in tr2.params.items():
            np.testing.assert_array_equal(np.asarray(v), saved[n])
        # and training continues on the smaller mesh
        loss = float(np.asarray(tr2.step(x, y)))
        assert np.isfinite(loss)


# --------------------------------------------- pipeline nan_guard ----------
@pytest.mark.faultinject
class TestPipelineNanGuard:
    @pytest.fixture(autouse=True)
    def _clean_mesh(self):
        yield
        dist_env.set_mesh(None)

    def _pipe_trainer(self, patience=1):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer, LayerDesc)
        from paddle_tpu.parallel import ParallelTrainer
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs['dp_degree'] = 2
        strategy.hybrid_configs['mp_degree'] = 1
        strategy.hybrid_configs['pp_degree'] = 2
        strategy.pipeline = True
        strategy.pipeline_configs['accumulate_steps'] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        H = 8
        ce = nn.MSELoss()
        pipe = PipelineLayer(
            [LayerDesc(nn.Linear, H, H), LayerDesc(nn.Tanh),
             LayerDesc(nn.Linear, H, H), LayerDesc(nn.Tanh)],
            num_stages=2, loss_fn=lambda out, yy: ce(out, yy))
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=pipe.parameters())
        import warnings
        with warnings.catch_warnings():
            # the old behaviour warned-and-disabled here; now it must
            # construct silently with the guard armed
            warnings.simplefilter('error')
            tr = ParallelTrainer(pipe, opt,
                                 lambda out, yy: ce(out, yy),
                                 strategy=strategy, nan_guard=True,
                                 nan_patience=patience)
        assert tr.nan_guard and tr.sentinel is not None
        return tr

    def test_nan_microbatch_skips_then_rolls_back(self, tmp_path,
                                                  chaos):
        """Acceptance gate: an injected NaN MICROBATCH under 1F1B
        triggers the device-side skip, the sentinel rollback restores
        the last committed sharded checkpoint, and training resumes."""
        tr = self._pipe_trainer(patience=1)
        rs = np.random.RandomState(0)
        H = 8
        x = rs.randn(8, H).astype('float32')
        y = rs.randn(8, H).astype('float32')
        l0 = float(np.asarray(tr.step(x, y)))
        assert np.isfinite(l0)
        assert tr._step_no == 1
        tr.save_checkpoint(str(tmp_path / 'ck'), async_save=False)
        good = {n: np.array(jnp.asarray(v)) for n, v in
                zip(('w0',), [jax.tree_util.tree_leaves(
                    tr.params)[0]])}

        eng = chaos({'seed': 0, 'faults': [
            Fault('nan_grads', at_step=2)]})
        # poison rows 4..7 = microbatch 1 of 2 (M=2, B=8): ONE
        # microbatch is non-finite, the rest stay clean — exactly the
        # per-microbatch reduction's job
        xbad = np.array(x, copy=True)
        xbad[4:] = eng.poison(2, x[4:])
        assert np.isnan(xbad[4:]).any() and not np.isnan(xbad[:4]).any()
        before_rb = len(_events('nan_rollback'))
        tr.step(xbad, y)
        assert tr._step_no == 1            # skipped, not applied
        assert tr.sentinel.rollbacks == 1  # patience=1 → rollback
        assert len(_events('nan_rollback')) == before_rb + 1
        leaf = np.asarray(jax.tree_util.tree_leaves(tr.params)[0])
        np.testing.assert_array_equal(leaf, good['w0'])
        assert np.isfinite(leaf).all()
        # training resumes from the committed step
        l2 = float(np.asarray(tr.step(x, y)))
        assert np.isfinite(l2)
        assert tr._step_no == 2

    def test_clean_pipeline_run_unaffected(self):
        """nan_guard=True must not perturb a healthy pipeline run:
        losses match the unguarded trainer exactly."""
        tr_g = self._pipe_trainer(patience=3)
        rs = np.random.RandomState(1)
        H = 8
        x = rs.randn(8, H).astype('float32')
        y = rs.randn(8, H).astype('float32')
        guarded = [float(np.asarray(tr_g.step(x, y)))
                   for _ in range(3)]
        assert tr_g._step_no == 3
        assert tr_g.sentinel.total_skipped == 0
        dist_env.set_mesh(None)

        from paddle_tpu.parallel import ParallelTrainer
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer, LayerDesc)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs['dp_degree'] = 2
        strategy.hybrid_configs['mp_degree'] = 1
        strategy.hybrid_configs['pp_degree'] = 2
        strategy.pipeline = True
        strategy.pipeline_configs['accumulate_steps'] = 2
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        ce = nn.MSELoss()
        pipe = PipelineLayer(
            [LayerDesc(nn.Linear, H, H), LayerDesc(nn.Tanh),
             LayerDesc(nn.Linear, H, H), LayerDesc(nn.Tanh)],
            num_stages=2, loss_fn=lambda out, yy: ce(out, yy))
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=pipe.parameters())
        tr_p = ParallelTrainer(pipe, opt, lambda out, yy: ce(out, yy),
                               strategy=strategy)
        plain = [float(np.asarray(tr_p.step(x, y))) for _ in range(3)]
        np.testing.assert_allclose(guarded, plain, rtol=1e-6)


# ------------------------------------------------- invariant checker -------
class TestCheckInvariants:
    def test_clean_dir_passes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / 'run'),
                                async_save=False)
        mgr.save(_tree(1), 1)
        mgr.save(_tree(2), 2)
        assert check_invariants(str(tmp_path / 'run')) == []

    def test_corrupt_committed_step_flagged(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        eng = ChaosEngine(FaultPlan(seed=0))
        eng._damage_dir(os.path.join(d, 'step_1'), flip=True)
        out = check_invariants(d)
        assert any(v.startswith('I1') for v in out)

    def test_restore_of_uncommitted_step_flagged(self, tmp_path):
        d = str(tmp_path / 'run')
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(_tree(1), 1)
        events = [
            {'kind': 'checkpoint_commit', 'step': 1},
            {'kind': 'span', 'name': 'checkpoint_restore', 'step': 9},
        ]
        out = check_invariants(d, events=events)
        assert any(v.startswith('I3') for v in out)

    def test_preempt_code_and_budget(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / 'run'),
                                async_save=False)
        mgr.save(_tree(1), 1)
        out = check_invariants(str(tmp_path / 'run'),
                               preempt_codes=[1],
                               max_restarts=1, restarts=3)
        assert any(v.startswith('I4') for v in out)
        assert any(v.startswith('I5') for v in out)


# ------------------------------------------------- check_ckpt --deep -------
@pytest.mark.faultinject
class TestCheckCkptDeep:
    def _run(self, *args):
        import contextlib
        import io
        import types
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = _check_ckpt().main(list(args))
        return types.SimpleNamespace(returncode=rc,
                                     stdout=buf.getvalue(), stderr='')

    def _committed(self, tmp_path, hosts=None):
        d = str(tmp_path / 'run')
        if hosts:
            p = os.path.join(d, 'step_1')
            save_sharded(_tree(1), p, async_save=False, commit=False)
            rels = [rel for rel, _ in _walk(p)]
            split = [rels[i::hosts] for i in range(hosts)]
            for h in range(hosts):
                M.write_intent(p, h, step=1, files=split[h])
            M.finalize_two_phase(p, hosts, step=1, timeout=5)
        else:
            CheckpointManager(d, async_save=False).save(_tree(1), 1)
        return d

    def test_deep_ok_exits_zero(self, tmp_path):
        d = self._committed(tmp_path)
        p = self._run(d, '--deep')
        assert p.returncode == 0, p.stdout
        assert 'ok (deep)' in p.stdout

    def test_torn_exits_3(self, tmp_path):
        d = self._committed(tmp_path)
        ChaosEngine(FaultPlan(seed=0))._damage_dir(
            os.path.join(d, 'step_1'), flip=False)   # truncate
        p = self._run(d, '--deep')
        assert p.returncode == 3, (p.returncode, p.stdout)
        assert 'size' in p.stdout

    def test_digest_mismatch_exits_5(self, tmp_path):
        d = self._committed(tmp_path)
        ChaosEngine(FaultPlan(seed=0))._damage_dir(
            os.path.join(d, 'step_1'), flip=True)    # byte flip
        p = self._run(d, '--deep')
        assert p.returncode == 5, (p.returncode, p.stdout)
        assert 'mismatch' in p.stdout

    def test_missing_host_exits_4(self, tmp_path):
        d = self._committed(tmp_path, hosts=2)
        step = os.path.join(d, 'step_1')
        doc = M.read_manifest(step)
        victims = [rel for rel, meta in doc['files'].items()
                   if meta['host'] == 1]
        assert victims
        for rel in victims:
            os.remove(os.path.join(step, rel))
        p = self._run(d, '--deep')
        assert p.returncode == 4, (p.returncode, p.stdout)
        assert 'host 1' in p.stdout

    def test_half_committed_classed_torn(self, tmp_path):
        d = str(tmp_path / 'run')
        p1 = os.path.join(d, 'step_1')
        save_sharded(_tree(1), p1, async_save=False, commit=False)
        M.write_intent(p1, 0, step=1)
        p = self._run(d, '--deep')
        assert p.returncode == 3, (p.returncode, p.stdout)
        assert 'half-committed' in p.stdout

    def test_shallow_mode_unchanged(self, tmp_path):
        d = self._committed(tmp_path)
        p = self._run(d)
        assert p.returncode == 0
        assert p.stdout.strip().endswith('1')


# ------------------------------------------------- run_report timeline -----
class TestRunReportTimeline:
    def test_faults_and_barrier_spans_in_timeline(self, tmp_path):
        """run_report's resilience timeline shows injected faults and
        2-phase commit barrier spans alongside the classic events."""
        rows = [
            {'kind': 'steps', 'ts': 1.0, 'rank': 0, 'tag': 'train',
             'n': 1, 'step_time_ms': [1.0]},
            {'kind': 'fault_injected', 'ts': 2.0, 'rank': 0,
             'fault': 'sigkill', 'seed': 7, 'step': 5},
            {'kind': 'span', 'name': 'commit_barrier', 'ts': 3.0,
             'rank': 0, 'dur_s': 0.2, 'hosts': 4},
            {'kind': 'commit_finalize', 'ts': 3.2, 'rank': 0,
             'step': 6, 'hosts': 4},
            {'kind': 'reshape_restore', 'ts': 4.0, 'rank': 0,
             'step': 6, 'saved_mesh': {'dp': 8}, 'mesh': {'dp': 4}},
            {'kind': 'retry', 'ts': 4.5, 'rank': 0, 'attempt': 0,
             'delay_s': 0.1},
            {'kind': 'span', 'name': 'compile', 'ts': 5.0, 'rank': 0,
             'dur_s': 1.0},
        ]
        f = tmp_path / 'telemetry-r0.jsonl'
        f.write_text('\n'.join(json.dumps(r) for r in rows) + '\n')
        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, 'tools',
                                          'run_report.py'),
             str(f), '--json'],
            capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        doc = json.loads(p.stdout)
        kinds = [r['kind'] for r in doc['timeline']]
        assert 'fault_injected' in kinds
        assert 'span:commit_barrier' in kinds
        assert 'reshape_restore' in kinds
        assert 'retry' in kinds
        assert 'span:compile' not in kinds      # ordinary spans stay out
        fault = next(r for r in doc['timeline']
                     if r['kind'] == 'fault_injected')
        assert fault['fault'] == 'sigkill' and fault['seed'] == 7
        barrier = next(r for r in doc['timeline']
                       if r['kind'] == 'span:commit_barrier')
        assert barrier['hosts'] == 4 and barrier['dur_s'] == 0.2
