"""analysis.threads (AST concurrency lint) + analysis.lockcheck
(opt-in runtime lock checker).

Positive AND negative fixture per rule, the locked-by refinement, the
suppression grammar, the ABBA lock-order cycle fixture, guard_object
violation/clean paths, the `lockcheck` telemetry event, CLI --threads
exit codes + --json schema, the tier-1 self-lint gate over all of
paddle_tpu/, a chaos composition run (checker armed under collective
faults), and the loader thread-leak assertions the lifecycle rule's
fixes guarantee.  (File name sorts before test_host_embedding so the
whole module runs inside the tier-1 window.)
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from paddle_tpu import analysis, telemetry
from paddle_tpu.analysis import lockcheck
from paddle_tpu.analysis.threads import (
    lint_threads_source, lint_threads_sources, THREAD_RULES)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, **kw):
    return lint_threads_source(textwrap.dedent(src), **kw)


def _rules(findings, rule):
    return [f for f in findings if f.rule == rule]


# ======================================================= rule: guarded-by ==

GUARDED_BAD = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0          # guarded-by: _lock

        def start(self):
            t = threading.Thread(target=self._run, daemon=True)
            t.start()
            t.join(timeout=1)

        def _run(self):
            self.count += 1
"""


class TestGuardedBy:
    def test_seeded_violation_flags_high(self):
        fs = _rules(_lint(GUARDED_BAD), 'guarded-by')
        assert len(fs) == 1
        assert fs[0].severity == 'high'
        assert 'Worker._run' in fs[0].message
        assert 'self.count' in fs[0].message

    def test_access_under_lock_is_clean(self):
        fs = _lint(GUARDED_BAD.replace(
            '            self.count += 1',
            '            with self._lock:\n'
            '                self.count += 1'))
        assert not _rules(fs, 'parse-error')
        assert not _rules(fs, 'guarded-by')

    def test_init_exempt(self):
        # the seeded fixture's __init__ writes self.count unlocked and
        # is NOT flagged (construction happens-before publication)
        fs = _rules(_lint(GUARDED_BAD), 'guarded-by')
        assert all('__init__' not in f.message for f in fs)

    def test_guarded_by_class_map_variant(self):
        fs = _rules(_lint("""
            import threading

            class Worker:
                _GUARDED_BY = {'count': '_lock'}

                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0

                def start(self):
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)
                    self._t.start()

                def stop(self):
                    self._t.join(timeout=1)

                def _run(self):
                    self.count += 1
        """), 'guarded-by')
        assert len(fs) == 1 and fs[0].severity == 'high'

    def test_subscribe_callback_is_entry_point(self):
        # subscriber callbacks run on whatever thread emits — write()
        # must be treated exactly like a Thread target
        fs = _rules(_lint("""
            import threading

            class Agg:
                def __init__(self, rec):
                    self._lock = threading.Lock()
                    self.total = 0      # guarded-by: _lock
                    rec.subscribe(self.write)

                def write(self, rec):
                    self.total += 1
        """), 'guarded-by')
        assert len(fs) == 1 and fs[0].severity == 'high'

    def test_unreachable_method_warns_not_high(self):
        fs = _rules(_lint("""
            import threading

            class Holder:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0          # guarded-by: _lock

                def bump(self):
                    self.n += 1
        """), 'guarded-by')
        assert len(fs) == 1 and fs[0].severity == 'warn'

    def test_locked_by_refinement_silences(self):
        # the per-kind handler pattern: dispatched under the caller's
        # `with self._lock` — the annotation is a claim, not a mute
        fs = _lint(GUARDED_BAD.replace(
            '    def _run(self):',
            '    def _run(self):  # locked-by: _lock'))
        assert not _rules(fs, 'guarded-by')

    def test_suppression_comment(self, tmp_path):
        # suppression scans the flagged line's source via linecache —
        # exercise it the way the sweep does, on a real file
        p = tmp_path / 'sup.py'
        p.write_text(textwrap.dedent(GUARDED_BAD.replace(
            '            self.count += 1',
            '            self.count += 1'
            '  # tpu-lint: disable=guarded-by')))
        rep = lint_threads_sources([str(p)])
        assert not _rules(rep.findings, 'guarded-by')

    def test_wrong_lock_still_flags(self):
        fs = _lint(GUARDED_BAD.replace(
            '            self.count += 1',
            '            with self._other:\n'
            '                self.count += 1'))
        assert not _rules(fs, 'parse-error')
        assert len(_rules(fs, 'guarded-by')) == 1


# ============================================== rule: blocking-under-lock ==

def _blocking_src(cls_name):
    return f"""
        import threading
        import time

        class {cls_name}:
            def __init__(self):
                self._lock = threading.Lock()

            def tick(self):
                with self._lock:
                    time.sleep(0.1)
    """


class TestBlockingUnderLock:
    def test_hot_class_is_high(self):
        fs = _rules(_lint(_blocking_src('StatsAggregator')),
                    'blocking-under-lock')
        assert len(fs) == 1 and fs[0].severity == 'high'
        assert 'sleep' in fs[0].message

    def test_cold_class_is_warn(self):
        fs = _rules(_lint(_blocking_src('Widget')),
                    'blocking-under-lock')
        assert len(fs) == 1 and fs[0].severity == 'warn'

    def test_open_and_post_flagged(self):
        fs = _rules(_lint("""
            class Publisher:
                def flush(self):
                    with self._lock:
                        open('/tmp/x').read()
                        self.transport.post(b'frame')
        """), 'blocking-under-lock')
        assert len(fs) == 2
        assert all(f.severity == 'high' for f in fs)

    def test_non_lock_with_ignored(self):
        fs = _lint("""
            class Writer:
                def flush(self):
                    with self._file:
                        open('/tmp/x').read()
        """)
        assert not _rules(fs, 'blocking-under-lock')

    def test_nested_def_not_charged_to_lock(self):
        # a closure defined under the lock runs LATER, off-lock
        fs = _lint("""
            import time

            class Sched:
                def plan(self):
                    with self._lock:
                        def later():
                            time.sleep(1)
                        self.cb = later
        """)
        assert not _rules(fs, 'blocking-under-lock')

    def test_after_release_is_clean(self):
        fs = _lint("""
            import time

            class StatsAggregator:
                def tick(self):
                    with self._lock:
                        snap = dict(self.state)
                    time.sleep(0.1)
        """)
        assert not _rules(fs, 'blocking-under-lock')


# ========================================== rule: daemon-thread-lifecycle ==

class TestDaemonLifecycle:
    def test_orphan_daemon_warns(self):
        fs = _rules(_lint("""
            import threading

            def fire():
                threading.Thread(target=print, daemon=True).start()
        """), 'daemon-thread-lifecycle')
        assert len(fs) == 1 and fs[0].severity == 'warn'

    def test_join_in_scope_is_clean(self):
        fs = _lint("""
            import threading

            def fire():
                t = threading.Thread(target=print, daemon=True)
                t.start()
                t.join(timeout=2.0)
        """)
        assert not _rules(fs, 'daemon-thread-lifecycle')

    def test_self_thread_with_stop_method_is_clean(self):
        fs = _lint("""
            import threading

            class Svc:
                def start(self):
                    self._thread = threading.Thread(
                        target=self._run, daemon=True)
                    self._thread.start()

                def stop(self):
                    self._stop.set()
        """)
        assert not _rules(fs, 'daemon-thread-lifecycle')

    def test_self_thread_without_stop_warns(self):
        fs = _rules(_lint("""
            import threading

            class Svc:
                def start(self):
                    self._thread = threading.Thread(
                        target=self._run, daemon=True)
                    self._thread.start()
        """), 'daemon-thread-lifecycle')
        assert len(fs) == 1

    def test_non_daemon_ignored(self):
        fs = _lint("""
            import threading

            def fire():
                threading.Thread(target=print).start()
        """)
        assert not _rules(fs, 'daemon-thread-lifecycle')

    def test_str_join_does_not_count(self):
        fs = _rules(_lint("""
            import threading

            def fire(parts):
                threading.Thread(target=print, daemon=True).start()
                return ','.join(parts)
        """), 'daemon-thread-lifecycle')
        assert len(fs) == 1


# =============================================== registry / entry points ===

class TestRegistryAndSweep:
    def test_three_rules_registered(self):
        assert set(THREAD_RULES) >= {'guarded-by', 'blocking-under-lock',
                                     'daemon-thread-lifecycle'}

    def test_disable_skips_rule(self):
        fs = _lint(GUARDED_BAD, disable=('guarded-by',))
        assert not _rules(fs, 'guarded-by')

    def test_sweep_report_extras(self, tmp_path):
        (tmp_path / 'mod.py').write_text(textwrap.dedent(GUARDED_BAD))
        rep = lint_threads_sources([str(tmp_path)])
        assert rep.extras['threads']['files'] == 1
        assert rep.counts()['high'] == 1

    def test_syntax_error_degrades_to_info(self):
        fs = _lint('def broken(:\n')
        assert len(fs) == 1 and fs[0].rule == 'parse-error'
        assert fs[0].severity == 'info'


# ================================================== tier-1 self-lint gate ==

class TestSelfLintGate:
    def test_paddle_tpu_has_zero_high(self):
        rep = lint_threads_sources([os.path.join(REPO, 'paddle_tpu')])
        high = [f for f in rep if f.severity == 'high']
        assert not high, analysis.LintReport(high).render(high)

    def test_paddle_tpu_has_zero_warn(self):
        # the satellites fixed every daemon-lifecycle WARN at its
        # source (sentinel shutdown + bounded joins) — keep it that way
        rep = lint_threads_sources([os.path.join(REPO, 'paddle_tpu')])
        assert not len(rep), str(rep)


# ================================================================== CLI ====

def _cli(*args, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'tpu_lint.py'),
         *args], capture_output=True, text=True, env=env, cwd=cwd)


class TestCLI:
    def test_clean_file_exits_0(self, tmp_path):
        p = tmp_path / 'ok.py'
        p.write_text('x = 1\n')
        r = _cli(str(p), '--threads')
        assert r.returncode == 0, r.stdout + r.stderr

    def test_high_finding_exits_1_and_json_schema(self, tmp_path):
        p = tmp_path / 'bad.py'
        p.write_text(textwrap.dedent(GUARDED_BAD))
        r = _cli(str(p), '--threads', '--json')
        assert r.returncode == 1, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc['counts']['high'] == 1
        assert doc['extras']['threads']['files'] == 1
        (f,) = [x for x in doc['findings']
                if x['rule'] == 'guarded-by']
        assert f['severity'] == 'high'
        assert f['file'] == str(p) and f['line']
        assert f['origin'] == 'ast'

    def test_threads_without_paths_is_usage_error(self):
        r = _cli('--threads')
        assert r.returncode == 2

    def test_fail_on_never_exits_0(self, tmp_path):
        p = tmp_path / 'bad.py'
        p.write_text(textwrap.dedent(GUARDED_BAD))
        r = _cli(str(p), '--threads', '--fail-on', 'never')
        assert r.returncode == 0

    def test_self_lint_gate_cli(self):
        r = _cli('paddle_tpu/', '--threads')
        assert r.returncode == 0, r.stdout + r.stderr


# ========================================================== lockcheck ======

class TestResolveLockcheck:
    def test_explicit_false_beats_env(self, monkeypatch):
        monkeypatch.setenv(lockcheck.LOCKCHECK_ENV, '1')
        assert lockcheck.resolve_lockcheck(False) is False

    def test_explicit_true(self, monkeypatch):
        monkeypatch.setenv(lockcheck.LOCKCHECK_ENV, '0')
        assert lockcheck.resolve_lockcheck(True) is True

    def test_env_decides_when_none(self, monkeypatch):
        monkeypatch.setenv(lockcheck.LOCKCHECK_ENV, '1')
        assert lockcheck.resolve_lockcheck(None) is True
        for off in ('', '0', 'off', 'false', 'no'):
            monkeypatch.setenv(lockcheck.LOCKCHECK_ENV, off)
            assert lockcheck.resolve_lockcheck(None) is False

    def test_maybe_install_off_yields_none(self, monkeypatch):
        monkeypatch.setenv(lockcheck.LOCKCHECK_ENV, '0')
        with lockcheck.maybe_install() as chk:
            assert chk is None
        assert threading.Lock is lockcheck._REAL_LOCK


def _abba(chk, swap=False):
    """Two serialized threads acquiring two wrapped locks in opposite
    (or, with swap=False... same) order.  Serialization via events so
    the fixture can never actually deadlock."""
    a = chk.wrap(name='lockA')
    b = chk.wrap(name='lockB')
    gate1, gate2 = threading.Event(), threading.Event()

    def t1():
        with a:
            with b:
                pass
        gate1.set()

    def t2():
        gate1.wait(timeout=5)
        first, second = (b, a) if swap else (a, b)
        with first:
            with second:
                pass
        gate2.set()

    th1 = threading.Thread(target=t1)
    th2 = threading.Thread(target=t2)
    th1.start(), th2.start()
    th1.join(timeout=5), th2.join(timeout=5)
    assert gate2.is_set()
    return chk


class TestLockOrderCycles:
    def test_abba_cycle_detected(self):
        chk = _abba(lockcheck.LockChecker(), swap=True)
        cycles = chk.cycles()
        assert cycles and set(cycles[0]) == {'lockA', 'lockB'}
        rep = chk.report()
        fs = [f for f in rep if f.rule == 'lock-order-cycle']
        assert len(fs) == 1 and fs[0].severity == 'high'
        assert 'lockA' in fs[0].message and 'lockB' in fs[0].message
        # first-seen acquisition stacks name this test file
        assert 'test_analysis_threads' in fs[0].message

    def test_consistent_order_is_clean(self):
        chk = _abba(lockcheck.LockChecker(), swap=False)
        assert not chk.cycles()
        assert not [f for f in chk.report()
                    if f.rule == 'lock-order-cycle']

    def test_rlock_reentry_adds_no_edge(self):
        chk = lockcheck.LockChecker()
        r = chk.wrap(rlock=True, name='re')
        with r:
            with r:
                pass
        assert not chk._edges

    def test_hold_stats_recorded(self):
        chk = lockcheck.LockChecker()
        lk = chk.wrap(name='held')
        with lk:
            time.sleep(0.01)
        st = chk.hold_stats()['held']
        assert st['count'] == 1 and st['max_ms'] >= 5.0


class TestGuardObject:
    class Box:
        # RLock on purpose: guard_object can interrogate an RLock's
        # owner (_is_owned); a plain Lock's holder is unknowable, so
        # plain-Lock guards only activate through CheckedLock wrappers
        def __init__(self, lock=None):
            self._lock = lock if lock is not None else threading.RLock()
            self.val = 0

    def _cross_thread(self, fn):
        err = []

        def run():
            try:
                fn()
            except Exception as e:      # noqa: BLE001 - test harness
                err.append(e)

        t = threading.Thread(target=run)
        t.start()
        t.join(timeout=5)
        assert not err, err

    def test_unlocked_cross_thread_access_flagged(self):
        chk = lockcheck.LockChecker()
        box = self.Box()
        chk.guard_object(box, ('val',))
        self._cross_thread(lambda: setattr(box, 'val', 7))
        fs = [f for f in chk.report() if f.rule == 'unguarded-access']
        assert len(fs) == 1 and fs[0].severity == 'high'
        assert 'Box.val' in fs[0].message

    def test_locked_access_and_owner_thread_clean(self):
        chk = lockcheck.LockChecker()
        box = self.Box(lock=chk.wrap(name='box'))
        chk.guard_object(box, ('val',))
        box.val = 1                     # owner thread: exempt

        def locked():
            with box._lock:
                box.val = 2

        self._cross_thread(locked)
        assert not [f for f in chk.report()
                    if f.rule == 'unguarded-access']

    def test_unguard_restores_class(self):
        box = self.Box()
        orig = type(box)
        with lockcheck.install(scope=None) as chk:
            chk.guard_object(box, ('val',))
            assert type(box) is not orig
        assert type(box) is orig


class TestInstall:
    def test_factories_patched_and_restored(self):
        with lockcheck.install(scope=None) as chk:
            assert threading.Lock is not lockcheck._REAL_LOCK
            lk = threading.Lock()
            assert isinstance(lk, lockcheck.CheckedLock)
            assert chk.locks_created >= 1
        assert threading.Lock is lockcheck._REAL_LOCK
        assert threading.RLock is lockcheck._REAL_RLOCK

    def test_scope_filters_foreign_frames(self):
        # this test file is outside the 'paddle_tpu' scope: Lock()
        # constructed here stays a plain lock (so queue/threading
        # internals are never wrapped in real runs either)
        with lockcheck.install(scope='paddle_tpu'):
            lk = threading.Lock()
            assert not isinstance(lk, lockcheck.CheckedLock)

    def test_double_install_raises(self):
        with lockcheck.install(scope=None):
            with pytest.raises(RuntimeError):
                with lockcheck.install(scope=None):
                    pass                # pragma: no cover

    def test_disarm_emits_lockcheck_telemetry(self):
        before = len(list(telemetry.events('lockcheck')))
        with lockcheck.install(scope=None) as chk:
            with chk.wrap(name='x'):
                pass
        evs = list(telemetry.events('lockcheck'))
        assert len(evs) == before + 1
        ev = evs[-1]
        assert ev['locks'] >= 1 and ev['cycles'] == 0
        assert ev['max_hold_lock'] == 'x'

    def test_condition_over_checked_lock_works(self):
        # Condition needs _is_owned/_release_save etc. — __getattr__
        # delegation must keep the protocol alive on a wrapped RLock
        chk = lockcheck.LockChecker()
        cv = threading.Condition(chk.wrap(rlock=True, name='cv'))
        hit = []

        def waiter():
            with cv:
                if cv.wait(timeout=5):
                    hit.append(True)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cv:
            cv.notify()
        t.join(timeout=5)
        assert hit == [True]


# ================================================ chaos composition ========

@pytest.mark.faultinject
class TestChaosComposition:
    def test_armed_checker_survives_collective_faults(self, tmp_path,
                                                      chaos):
        """Lockcheck armed while collective-layer faults fire: the
        checker must neither deadlock nor crash, and the faulted run
        must fail exactly the way it fails unarmed."""
        from paddle_tpu.distributed.collective import (
            FileKVStore, HostCollectives, CollectiveTimeout)
        from paddle_tpu.resilience.chaos import Fault

        chaos({'seed': 7, 'faults': [
            Fault('collective_delay', rank=0, at_step=None, count=2,
                  delay_s=0.02).to_dict(),
            Fault('collective_drop', rank=1, at_step=None,
                  count=1).to_dict()]})
        with lockcheck.install() as chk:
            kv = FileKVStore(str(tmp_path / 'kv'))
            t0 = HostCollectives(client=kv, rank=0, world=2,
                                 timeout_s=0.5)
            t1 = HostCollectives(client=kv, rank=1, world=2,
                                 timeout_s=0.5)
            res, errs = {}, {}

            def run(r, t):
                try:
                    res[r] = t.allreduce(np.ones(2), 'sum', tag='c')
                except Exception as e:  # noqa: BLE001 - expected
                    errs[r] = e

            ts = [threading.Thread(target=run, args=(r, t))
                  for r, t in ((0, t0), (1, t1))]
            for th in ts:
                th.start()
            for th in ts:
                th.join(timeout=30)
            assert all(not th.is_alive() for th in ts), \
                'armed checker deadlocked a faulted collective'
            # the drop still surfaces as the usual failure pair
            assert isinstance(errs.get(0), CollectiveTimeout)
            assert isinstance(errs.get(1), RuntimeError)
            rep = chk.report()
            assert not [f for f in rep
                        if f.rule == 'lock-order-cycle'], str(rep)
        assert threading.Lock is lockcheck._REAL_LOCK


class TestArmedWorkloads:
    def test_trainer_and_engine_clean_and_unperturbed(self):
        """The runtime half of the concurrency posture: a dp=8
        trainer takes real steps and a serving engine completes a
        Poisson load while every paddle_tpu lock is instrumented.  No
        lock-order cycle, no unguarded access, a clean engine audit,
        and the unarmed re-run of the same trainer yields bit-equal
        losses (observation does not perturb training)."""
        import jax
        import paddle_tpu as paddle
        from paddle_tpu import nn, distributed as dist
        from paddle_tpu.distributed import env as dist_env
        from paddle_tpu.models.gpt import gpt_tiny
        from paddle_tpu.parallel import ParallelTrainer
        from paddle_tpu.serving import (ServeConfig, ServingEngine,
                                        poisson_requests)
        if jax.device_count() < 8:
            pytest.skip('needs 8 devices')
        rs = np.random.RandomState(1)
        X = rs.randn(16, 64).astype('float32')
        Y = rs.randn(16, 64).astype('float32')

        def trainer_losses(steps=6):
            dist_env.set_mesh(None)
            dist.init_parallel_env(axes={'dp': 8})
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(64, 256), nn.ReLU(),
                                nn.Linear(256, 64))
            opt = paddle.optimizer.Momentum(
                learning_rate=0.01, parameters=net.parameters())
            tr = ParallelTrainer(net, opt,
                                 lambda o, y: ((o - y) ** 2).mean())
            return [float(np.asarray(tr.step(X, Y)))
                    for _ in range(steps)]

        try:
            with lockcheck.install() as chk:
                armed = trainer_losses()
                dist_env.set_mesh(None)
                paddle.seed(7)
                model = gpt_tiny(num_layers=2, hidden_size=32,
                                 num_heads=2, max_seq_len=64)
                eng = ServingEngine(model, ServeConfig(
                    block_size=4, max_slots=4, decode_span=2,
                    prompt_buckets=(4, 8), batch_buckets=(1, 2, 4),
                    prefill_batch=2, max_model_len=32))
                rep = eng.run(poisson_requests(
                    8, rate_rps=500.0, prompt_lens=(3, 5, 8),
                    new_tokens=(4, 6),
                    vocab_size=model.config.vocab_size, seed=3))
                assert rep['decoded_tokens'] and rep['audit'] == []
                assert chk.locks_created > 0
                lrep = chk.report()
                assert not [f for f in lrep if f.rule in (
                    'lock-order-cycle', 'unguarded-access')], str(lrep)
            assert trainer_losses() == armed
        finally:
            dist_env.set_mesh(None)


# ============================================= loader thread-leak guard ====

def _paddle_threads():
    """Live non-main threads running paddle_tpu code (by target repr /
    thread name) — the leak detector's census."""
    time.sleep(0.05)        # let bounded joins finish their tick
    return [t for t in threading.enumerate()
            if t is not threading.main_thread() and t.is_alive()
            and t.daemon]


class TestNoOrphanThreads:
    def test_dataloader_teardown_leaves_no_threads(self):
        from paddle_tpu import io

        class DS(io.Dataset):
            def __len__(self):
                return 64

            def __getitem__(self, i):
                return np.full((4,), i, dtype='float32')

        before = len(_paddle_threads())
        dl = io.DataLoader(DS(), batch_size=8, num_workers=2)
        it = iter(dl)
        next(it)
        it.close()              # abandon mid-epoch
        del it
        for _ in range(100):    # bounded joins: <=0.1s poll + join
            if len(_paddle_threads()) <= before:
                break
            time.sleep(0.05)
        assert len(_paddle_threads()) <= before, \
            threading.enumerate()

    def test_buffered_reader_early_stop_joins_producer(self):
        from paddle_tpu import reader

        def gen():
            for i in range(1000):
                yield i

        before = len(_paddle_threads())
        r = reader.buffered(lambda: gen(), size=4)
        next(iter(reader.firstn(r, 3)()))
        for _ in range(100):
            if len(_paddle_threads()) <= before:
                break
            time.sleep(0.05)
        assert len(_paddle_threads()) <= before


# ===================================== regression: the fixed real races ====

class TestFixedRaces:
    def test_publisher_rate_gate_claims_slot_under_lock(self, tmp_path):
        """cluster.ClusterPublisher: the old unlocked check-then-act in
        maybe_publish let two subscriber threads both pass the rate
        gate and double-post one frame."""
        from paddle_tpu.telemetry.cluster import ClusterPublisher
        from paddle_tpu.distributed.collective import FileKVStore

        kv = FileKVStore(str(tmp_path / 'kv'))
        pub = ClusterPublisher(client=kv, rank=0, world=1,
                               interval_s=3600.0)
        posted = []
        pub.transport.post_stats = lambda frame: (
            posted.append(frame) or True)
        barrier = threading.Barrier(4)

        def racer():
            barrier.wait(timeout=5)
            pub.maybe_publish()

        ts = [threading.Thread(target=racer) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=5)
        assert len(posted) == 1
        assert pub.published == 1

    def test_live_install_is_idempotent_under_race(self):
        """live.LiveAggregator: racing install()s used to both
        subscribe, double-counting every event thereafter."""
        from paddle_tpu.telemetry.live import LiveAggregator
        from paddle_tpu.telemetry.recorder import get_recorder

        agg = LiveAggregator()
        rec = get_recorder()
        barrier = threading.Barrier(4)

        def racer():
            barrier.wait(timeout=5)
            agg.install()

        ts = [threading.Thread(target=racer) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=5)
        try:
            n = sum(1 for s in rec._subscribers if s == agg.write)
            assert n == 1
        finally:
            agg.uninstall()
        assert agg.write not in rec._subscribers

    def test_supervisor_counters_guarded_at_runtime(self):
        """resilience.PlanSupervisor: guard_object over the annotated
        counters catches any future unlocked write from the worker."""
        from paddle_tpu.resilience.supervisor import PlanSupervisor

        sup = PlanSupervisor.__new__(PlanSupervisor)
        chk = lockcheck.LockChecker()
        sup._lock = chk.wrap(name='supervisor')
        sup.swaps = 0
        sup.incidents = []
        chk.guard_object(sup, ('swaps', 'incidents'))

        def worker_write():
            with sup._lock:
                sup.swaps += 1          # locked: clean

        t = threading.Thread(target=worker_write)
        t.start()
        t.join(timeout=5)
        assert not [f for f in chk.report()
                    if f.rule == 'unguarded-access']

        def bad_write():
            sup.swaps += 1              # unlocked: flagged

        t = threading.Thread(target=bad_write)
        t.start()
        t.join(timeout=5)
        fs = [f for f in chk.report() if f.rule == 'unguarded-access']
        assert len(fs) == 1
        chk._unguard_all()
