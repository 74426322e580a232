"""True multi-host chaos: collective-layer fault seams, the
straggler/hang watchdog, property-based soak plans (PR 10).

Covers (fast, tier-1):
  * FileKVStore + HostCollectives: the host-side multi-process
    collective transport (dtype-agnostic crc-framed wire, bounded
    waits with missing-rank attribution, coordinated-abort flag);
  * the four collective-layer fault seams (delay / hang / drop /
    corrupt) + slow_rank throttling, seeded-deterministic, per-rank
    plan slicing, the restart fault ledger, and seam teardown when a
    worker dies mid-plan;
  * resilience.watchdog: step deadlines -> straggler/timeout
    escalation, heartbeat quorum, cost-model budget derivation,
    retry(deadline=) clamped by a collective budget;
  * ParallelTrainer(watchdog=...): a hung step escalates within the
    budget instead of deadlocking;
  * check_ckpt --deep --cluster (exit 7 on rank-set mismatch),
    save_host_shard/load_host_shard two-phase commits;
  * plangen: generation determinism/legality, shrinking, the golden
    fixtures soak_run --smoke gates on;
  * invariants I6/I7 + run_report's watchdog timeline/summary.

Slow (nothing automatic runs it): one 2-process ChaosCluster spin
of the built-in smoke plan — the old single-process chaos_run driver
cases folded into it — and a jax.distributed-initialized clean soak.
"""
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu.distributed.collective import (  # noqa: E402
    FileKVStore, HostCollectives, CollectiveTimeout,
    CollectivePayloadError, CoordinatedAbort)
from paddle_tpu.distributed.checkpoint import (  # noqa: E402
    save_host_shard, load_host_shard, latest_committed_step)
from paddle_tpu.resilience import manifest as M  # noqa: E402
from paddle_tpu.resilience import plangen  # noqa: E402
from paddle_tpu.resilience.chaos import (  # noqa: E402
    ChaosEngine, ChaosCluster, Fault, FaultPlan, check_invariants)
from paddle_tpu.resilience.retry import retry  # noqa: E402
from paddle_tpu.resilience.watchdog import (  # noqa: E402
    Budget, Watchdog, collective_budget, remaining_budget,
    resolve_watchdog, WATCHDOG_EXIT_CODE)
from paddle_tpu import telemetry  # noqa: E402


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, 'tools', f'{name}.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pair(tmp_path, timeout_s=5.0):
    kv = FileKVStore(str(tmp_path / 'kv'))
    return (HostCollectives(client=kv, rank=0, world=2,
                            timeout_s=timeout_s),
            HostCollectives(client=kv, rank=1, world=2,
                            timeout_s=timeout_s))


def _both(fn0, fn1):
    """Run two rank closures concurrently; returns ({rank: result},
    {rank: exception})."""
    res, errs = {}, {}

    def run(r, fn):
        try:
            res[r] = fn()
        except Exception as e:         # noqa: BLE001 - test harness
            errs[r] = e

    ts = [threading.Thread(target=run, args=(r, f))
          for r, f in ((0, fn0), (1, fn1))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    return res, errs


# =========================================================== transport ======

class TestFileKVStore:
    def test_roundtrip_and_delete(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        kv.key_value_set_bytes('a/b/c', b'\x00\xffpayload')
        assert kv.blocking_key_value_get_bytes('a/b/c', 100) \
            == b'\x00\xffpayload'
        assert kv.try_get_bytes('missing') is None
        kv.key_value_delete('a/b/c')
        assert kv.try_get_bytes('a/b/c') is None

    def test_blocking_get_times_out(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            kv.blocking_key_value_get_bytes('nope', 150)
        assert time.monotonic() - t0 < 2.0

    def test_blocking_get_sees_late_write(self, tmp_path):
        kv = FileKVStore(str(tmp_path))

        def writer():
            time.sleep(0.1)
            kv.key_value_set_bytes('late', b'x')

        threading.Thread(target=writer).start()
        assert kv.blocking_key_value_get_bytes('late', 3000) == b'x'


class TestHostCollectives:
    def test_allreduce_sum_and_mean(self, tmp_path):
        t0, t1 = _pair(tmp_path)
        res, errs = _both(
            lambda: t0.allreduce(np.full(4, 1.0, np.float32), 'sum',
                                 tag='s'),
            lambda: t1.allreduce(np.full(4, 3.0, np.float32), 'sum',
                                 tag='s'))
        assert not errs
        np.testing.assert_array_equal(res[0], np.full(4, 4.0, 'f4'))
        np.testing.assert_array_equal(res[0], res[1])

    def test_wire_is_dtype_agnostic_int8(self, tmp_path):
        """The EQuARX precondition: a quantized int8 payload frames,
        verifies and reduces through the SAME wire as f32."""
        t0, t1 = _pair(tmp_path)
        res, errs = _both(
            lambda: t0.allreduce(np.full(8, 2, np.int8), 'sum',
                                 tag='q'),
            lambda: t1.allreduce(np.full(8, 3, np.int8), 'sum',
                                 tag='q'))
        assert not errs
        assert res[0].dtype == np.int8
        np.testing.assert_array_equal(res[0], np.full(8, 5, np.int8))

    def test_allgather_object_and_broadcast(self, tmp_path):
        t0, t1 = _pair(tmp_path)
        res, errs = _both(
            lambda: t0.allgather_object({'r': 0}, tag='g'),
            lambda: t1.allgather_object({'r': 1}, tag='g'))
        assert not errs
        assert res[0] == [{'r': 0}, {'r': 1}] == res[1]
        res, errs = _both(
            lambda: t0.broadcast_object('payload', src=0, tag='b'),
            lambda: t1.broadcast_object(None, src=0, tag='b'))
        assert not errs
        assert res[1] == 'payload'

    def test_timeout_names_missing_ranks_and_emits_event(self,
                                                         tmp_path):
        t0, _ = _pair(tmp_path)
        telemetry.reset()
        with pytest.raises(CollectiveTimeout) as ei:
            t0.allreduce(np.ones(2), 'sum', tag='t', timeout_s=0.2)
        assert ei.value.missing == [1]
        evs = telemetry.events('timeout')
        assert evs and evs[-1]['missing'] == [1]
        assert evs[-1]['rank'] == 0

    def test_corrupt_frame_rejected(self, tmp_path):
        """crc framing catches wire corruption before any element is
        interpreted, whatever the dtype."""
        t0, t1 = _pair(tmp_path)
        orig_post = HostCollectives.post

        def evil_post(self, tag, op, payload):
            if self.rank == 1:
                b = bytearray(payload)
                b[-1] ^= 0x01
                payload = bytes(b)
            return orig_post(self, tag, op, payload)

        HostCollectives.post = evil_post
        try:
            res, errs = _both(
                lambda: t0.allreduce(np.ones(4, np.int8), 'sum',
                                     tag='c'),
                lambda: t1.allreduce(np.ones(4, np.int8), 'sum',
                                     tag='c'))
        finally:
            HostCollectives.post = orig_post
        assert isinstance(errs.get(0), CollectivePayloadError)
        assert errs[0].rank == 1

    def test_abort_flag_releases_waiters(self, tmp_path):
        t0, t1 = _pair(tmp_path, timeout_s=10.0)

        def waiter():
            return t0.allreduce(np.ones(2), 'sum', tag='w')

        def aborter():
            time.sleep(0.15)
            t1.request_abort('test')
            return 'aborted'

        t_start = time.monotonic()
        res, errs = _both(waiter, aborter)
        assert isinstance(errs.get(0), CoordinatedAbort)
        assert time.monotonic() - t_start < 5.0

    def test_stale_abort_ignored_after_restart(self, tmp_path):
        kv = FileKVStore(str(tmp_path / 'kv'))
        old = HostCollectives(client=kv, rank=0, world=2)
        old.request_abort('previous incarnation')
        time.sleep(0.02)
        fresh = HostCollectives(client=kv, rank=1, world=2)
        assert fresh.abort_requested() is None
        fresh.clear_abort()
        assert old.abort_requested() is None


# ======================================================= fault seams ========

@pytest.mark.faultinject
class TestCollectiveSeams:
    def test_delay_and_sequence_deterministic(self, tmp_path, chaos):
        plan = {'seed': 11, 'faults': [
            Fault('collective_delay', at_step=2, rank=0,
                  delay_s=0.05).to_dict(),
            Fault('slow_rank', at_step=2, rank=0,
                  delay_s=0.05).to_dict()]}
        seqs = []
        for run in range(2):
            t0, t1 = _pair(tmp_path / f'r{run}')
            eng = chaos(dict(plan))
            eng.rank = 0
            eng.step(1)
            eng.step(2)
            res, errs = _both(
                lambda: t0.allreduce(np.ones(2), 'sum', tag='d'),
                lambda: t1.allreduce(np.ones(2), 'sum', tag='d'))
            assert not errs
            seqs.append([(e['fault'], e.get('step'))
                         for e in eng.sequence()])
            eng.deactivate()
        assert seqs[0] == seqs[1] == [('slow_rank', 2),
                                      ('collective_delay', 2)]

    def test_hang_peer_times_out_abort_releases(self, tmp_path,
                                                chaos):
        eng = chaos({'seed': 3, 'faults': [
            Fault('collective_hang', rank=1, at_step=None, count=1,
                  delay_s=30.0).to_dict()]})
        t0, t1 = _pair(tmp_path, timeout_s=0.4)

        def r0():
            try:
                return t0.allreduce(np.ones(2), 'sum', tag='h')
            except CollectiveTimeout as e:
                t0.request_abort('timeout')
                raise e

        t_start = time.monotonic()
        res, errs = _both(
            r0, lambda: t1.allreduce(np.ones(2), 'sum', tag='h'))
        el = time.monotonic() - t_start
        assert isinstance(errs.get(0), CollectiveTimeout)
        assert isinstance(errs.get(1), CoordinatedAbort)
        assert el < 10.0, 'hung rank did not release on abort'
        assert [e['fault'] for e in eng.sequence()] \
            == ['collective_hang']

    def test_drop_raises_on_faulted_rank(self, tmp_path, chaos):
        chaos({'seed': 3, 'faults': [
            Fault('collective_drop', rank=1, at_step=None,
                  count=1).to_dict()]})
        t0, t1 = _pair(tmp_path, timeout_s=0.5)
        res, errs = _both(
            lambda: t0.allreduce(np.ones(2), 'sum', tag='x'),
            lambda: t1.allreduce(np.ones(2), 'sum', tag='x'))
        assert isinstance(errs.get(1), RuntimeError)
        assert 'injected participant drop' in str(errs[1])
        assert isinstance(errs.get(0), CollectiveTimeout)

    def test_corrupt_detected_by_receiver_any_dtype(self, tmp_path,
                                                    chaos):
        for run, dtype in enumerate((np.float32, np.int8)):
            eng = chaos({'seed': 5, 'faults': [
                Fault('collective_corrupt', rank=1, at_step=None,
                      count=1).to_dict()]})
            t0, t1 = _pair(tmp_path / f'd{run}')
            res, errs = _both(
                lambda: t0.allreduce(np.ones(4, dtype), 'sum',
                                     tag='cc'),
                lambda: t1.allreduce(np.ones(4, dtype), 'sum',
                                     tag='cc'))
            assert isinstance(errs.get(0), CollectivePayloadError), \
                (dtype, res, errs)
            assert errs[0].rank == 1
            eng.deactivate()

    def test_at_step_fault_inert_before_first_step(self, tmp_path,
                                                   chaos):
        """An at_step collective fault must not fire on startup
        collectives that run BEFORE the loop's first engine.step()
        (when the engine's current step is still None) — and must
        still fire at its step."""
        eng = chaos({'seed': 2, 'faults': [
            Fault('collective_corrupt', at_step=3, rank=1).to_dict()]})
        t0, t1 = _pair(tmp_path)
        res, errs = _both(
            lambda: t0.allreduce(np.ones(2), 'sum', tag='startup'),
            lambda: t1.allreduce(np.ones(2), 'sum', tag='startup'))
        assert not errs, errs         # startup exchange untouched
        assert eng.sequence() == []
        eng.step(3)
        res, errs = _both(
            lambda: t0.allreduce(np.ones(2), 'sum', tag='step3'),
            lambda: t1.allreduce(np.ones(2), 'sum', tag='step3'))
        assert isinstance(errs.get(0), CollectivePayloadError)
        assert [e['fault'] for e in eng.sequence()] \
            == ['collective_corrupt']

    def test_slice_for_rank_filters_and_keeps_seed(self):
        plan = FaultPlan(seed=9, faults=[
            Fault('sigkill', at_step=4, rank=0),
            Fault('collective_hang', at_step=5, rank=1),
            Fault('torn_write', path='step_2', count=2)])
        s0 = plan.slice_for_rank(0)
        s1 = plan.slice_for_rank(1)
        assert s0.seed == s1.seed == 9
        assert [f.kind for f in s0.faults] == ['sigkill', 'torn_write']
        assert [f.kind for f in s1.faults] == ['collective_hang',
                                               'torn_write']

    def test_mark_fired_ledger_stops_refire(self):
        plan = FaultPlan(seed=1, faults=[
            Fault('sigkill', at_step=4, rank=0),
            Fault('collective_hang', at_step=7, rank=0)])
        mine = plan.slice_for_rank(0)
        applied = mine.mark_fired(
            [{'kind': 'fault_injected', 'fault': 'sigkill', 'step': 4,
              'rank': 0}], rank=0)
        assert applied == 1
        assert mine.faults[0]._exhausted()          # won't re-kill
        assert not mine.faults[1]._exhausted()      # hang still armed

    def test_seam_restored_when_worker_dies_mid_plan(self, tmp_path):
        """The killed-worker teardown satellite: an engine whose
        scenario dies mid-plan (exception, SIGKILLed subprocess
        observed from the coordinator) must restore the collective
        seams on exit — mirroring the PR-5 reverse-order fix for the
        new seam class."""
        pristine = HostCollectives.post
        with pytest.raises(RuntimeError):
            with ChaosEngine(FaultPlan(seed=1, faults=[
                    Fault('collective_delay', at_step=None, count=1,
                          delay_s=0.01)])):
                assert HostCollectives.post is not pristine
                raise RuntimeError('worker died mid-plan')
        assert HostCollectives.post is pristine

    def test_stacked_engines_teardown_reverse(self):
        pristine = HostCollectives.post
        e1 = ChaosEngine(FaultPlan(seed=1)).activate()
        e2 = ChaosEngine(FaultPlan(seed=2)).activate()
        # reverse order restores the pristine function; forward order
        # would re-install e1's wrapper permanently
        e2.deactivate()
        e1.deactivate()
        assert HostCollectives.post is pristine


# ========================================================= watchdog =========

class TestWatchdog:
    def test_step_deadline_escalates_with_flight_dump(self, tmp_path):
        telemetry.reset()
        hits = []
        wd = Watchdog(budget=Budget(step_s=0.25, straggler_frac=0.4,
                                    grace_s=0.1),
                      name='t', on_escalate=hits.append,
                      flight_dir=str(tmp_path), poll=0.02)
        with wd:
            wd.step_started(3)
            time.sleep(0.7)
        assert hits and hits[0]['kind'] == 'timeout'
        assert hits[0]['step'] == 3
        kinds = [e['kind'] for e in wd.events]
        assert 'straggler' in kinds and 'timeout' in kinds
        evs = telemetry.events('timeout')
        assert evs and evs[-1]['budget_s'] == pytest.approx(0.25)
        assert hits[0].get('flight') and os.path.exists(
            hits[0]['flight'])

    def test_step_finished_disarms(self):
        hits = []
        wd = Watchdog(budget=Budget(step_s=0.2, grace_s=0.1),
                      on_escalate=hits.append, poll=0.02)
        with wd:
            wd.step_started(1)
            wd.step_finished(1)
            time.sleep(0.4)
        assert not hits

    def test_abort_flag_set_on_escalation(self, tmp_path):
        kv = FileKVStore(str(tmp_path / 'kv'))
        tr = HostCollectives(client=kv, rank=0, world=2)
        hits = []
        wd = Watchdog(budget=Budget(step_s=0.2, grace_s=0.1),
                      transport=tr, on_escalate=hits.append,
                      poll=0.02)
        with wd:
            wd.step_started(1)
            time.sleep(0.5)
        assert hits
        assert tr.abort_requested() is not None
        assert any(e['kind'] == 'coordinated_abort'
                   for e in wd.events)

    def test_peer_straggler_and_quorum_lost(self, tmp_path):
        kv = FileKVStore(str(tmp_path / 'kv'))
        tr = HostCollectives(client=kv, rank=0, world=3)
        # two peers heartbeated long ago, then went silent
        old = json.dumps({'ts': time.time() - 60, 'step': 1})
        kv.key_value_set_bytes('ptpu/hb/r1', old.encode())
        kv.key_value_set_bytes('ptpu/hb/r2', old.encode())
        hits = []
        wd = Watchdog(budget=Budget(step_s=30.0, grace_s=0.1),
                      transport=tr, peer_stale_s=1.0,
                      on_escalate=hits.append, poll=0.02,
                      heartbeat_interval=0.05)
        with wd:
            time.sleep(0.4)
        stragglers = [e for e in wd.events
                      if e['kind'] == 'straggler']
        assert {e['peer'] for e in stragglers} == {1, 2}
        assert hits and hits[0]['kind'] == 'quorum_lost'
        assert sorted(hits[0]['stale']) == [1, 2]

    def test_budget_parsing_and_costmodel_derivation(self):
        assert resolve_watchdog(False) is None
        assert resolve_watchdog(None) is None   # env default off
        b = Budget.from_env('step=12,collective=3,slack=4')
        assert b.step_s == 12 and b.collective_s == 3 and b.slack == 4
        assert Budget.from_env('0') is None
        assert Budget.from_env('1').effective_step_s() == 60.0
        d = Budget.from_costmodel(2_000_000, slack=8.0)  # 2s est
        assert d.step_s == pytest.approx(16.0)
        d = Budget.from_costmodel(10, slack=8.0)         # tiny est
        assert d.step_s == 5.0                           # min floor
        wd = resolve_watchdog({'step_s': 7})
        assert isinstance(wd, Budget) and wd.step_s == 7

    def test_collective_budget_from_started_watchdog(self, tmp_path):
        """Budget.collective_s is live configuration: a started
        Watchdog bounds every host collective's wait to it, and stop()
        restores the transport's own timeout."""
        from paddle_tpu.resilience.watchdog import default_collective_s
        t0, _ = _pair(tmp_path, timeout_s=30.0)
        wd = Watchdog(budget=Budget(step_s=60.0, collective_s=0.25,
                                    grace_s=0.1), poll=0.05)
        with wd:
            assert default_collective_s() == 0.25
            t_start = time.monotonic()
            with pytest.raises(CollectiveTimeout) as ei:
                t0.allreduce(np.ones(2), 'sum', tag='cb')
            assert time.monotonic() - t_start < 5.0
            assert ei.value.timeout == pytest.approx(0.25)
        assert default_collective_s() is None

    def test_watchdog_env_opt_in(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_WATCHDOG', 'step=9')
        b = resolve_watchdog(None)
        assert b is not None and b.step_s == 9
        assert resolve_watchdog(False) is None  # explicit off wins


class TestRetryClampedByCollectiveBudget:
    def test_retry_deadline_clamped(self):
        """A retry loop inside a collective deadline must not outlive
        the budget (satellite): retry(deadline=30) under a 0.3s
        collective budget gives up within it, and the telemetry
        records the clamp."""
        telemetry.reset()
        calls = []

        def flaky():
            calls.append(1)
            raise OSError('transient')

        t0 = time.monotonic()
        with collective_budget(0.3):
            assert remaining_budget() <= 0.3
            with pytest.raises(OSError):
                retry(flaky, retries=1000, backoff=0.04,
                      jitter=False, deadline=30.0)()
        elapsed = time.monotonic() - t0
        assert elapsed < 1.0, f'retry outlived the budget: {elapsed}'
        evs = telemetry.events('retry')
        assert evs, 'clamped retries must still be observable'
        assert evs[-1]['deadline_s'] <= 0.3
        assert evs[-1]['clamped_from_s'] == pytest.approx(30.0)

    def test_retry_unclamped_outside_budget(self):
        assert remaining_budget() is None
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError('x')
            return 'ok'

        assert retry(flaky, retries=5, backoff=0.01,
                     jitter=False, deadline=10.0)() == 'ok'

    def test_nested_budgets_take_minimum(self):
        with collective_budget(5.0):
            with collective_budget(0.2):
                assert remaining_budget() <= 0.2
            assert 0.2 < remaining_budget() <= 5.0


# ================================================== trainer watchdog ========

@pytest.mark.faultinject
class TestTrainerWatchdog:
    def _trainer(self, watchdog):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.parallel import ParallelTrainer
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 8), nn.Tanh())
        mse = nn.MSELoss()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=model.parameters())
        return ParallelTrainer(model, opt, lambda o, t: mse(o, t),
                               watchdog=watchdog)

    def test_hung_step_escalates_within_budget(self):
        """The acceptance path minus the process kill: a hung step
        under ParallelTrainer(watchdog=...) trips timeout -> flight
        dump -> escalation within the configured budget — the loop
        provably does not deadlock waiting for the step."""
        telemetry.reset()
        x = np.random.RandomState(0).randn(4, 8).astype('f4')
        y = np.zeros((4, 8), 'f4')
        tr = self._trainer({'step_s': 0.3, 'first_step_s': 30.0,
                            'grace_s': 0.1})
        tr.step(x, y)                       # compile + latch watchdog
        assert tr._watchdog is not None
        hits = []
        tr._watchdog.on_escalate = hits.append   # not os._exit in CI
        tr._watchdog.poll = 0.02
        orig = tr._compiled

        def hung(*a, **k):
            time.sleep(1.2)
            return orig(*a, **k)

        tr._compiled = hung
        t0 = time.monotonic()
        tr.step(x, y)
        elapsed = time.monotonic() - t0
        tr.stop_watchdog()
        assert hits and hits[0]['kind'] == 'timeout', hits
        assert elapsed < 10.0
        evs = telemetry.events('timeout')
        assert evs and evs[-1]['name'] == 'parallel'
        # stop_watchdog is FINAL: later steps run unwatched instead of
        # silently re-latching a fresh escalation-armed thread
        tr._compiled = orig
        tr.step(x, y)
        assert tr._watchdog is None

    def test_watchdog_off_by_default_and_false_beats_env(
            self, monkeypatch):
        x = np.random.RandomState(0).randn(4, 8).astype('f4')
        y = np.zeros((4, 8), 'f4')
        tr = self._trainer(None)
        tr.step(x, y)
        assert tr._watchdog is None
        monkeypatch.setenv('PADDLE_TPU_WATCHDOG', '1')
        tr2 = self._trainer(False)
        tr2.step(x, y)
        assert tr2._watchdog is None


# ============================================= per-host shard commits =======

@pytest.mark.faultinject
class TestHostShardCheckpoint:
    def _save_both(self, run, step, world=2, tamper_meta=None):
        save_host_shard(run, step, 1,
                        {'w': np.full(4, step + 1.0, 'f4')},
                        num_hosts=world)
        doc = save_host_shard(run, step, 0,
                              {'w': np.full(4, step + 0.0, 'f4')},
                              num_hosts=world, barrier_timeout=10.0)
        if tamper_meta:
            d = M.read_manifest(os.path.join(run, f'step_{step}'))
            d.update(tamper_meta)
            M.atomic_write(
                os.path.join(run, f'step_{step}', M.MANIFEST_NAME),
                lambda f: json.dump(d, f))
        return doc

    def test_two_phase_shard_save_and_restore(self, tmp_path):
        run = str(tmp_path / 'ckpt')
        doc = self._save_both(run, 2)
        assert doc['process_count'] == 2 and doc['hosts'] == 2
        hosts = {m['host'] for rel, m in doc['files'].items()
                 if rel.startswith('shard_')}
        assert hosts == {0, 1}
        assert latest_committed_step(run) == 2
        got = load_host_shard(run, 2, 1)
        np.testing.assert_array_equal(got['w'], np.full(4, 3.0, 'f4'))
        assert load_host_shard(run, 2, 7) is None

    def test_missing_ack_times_out_uncommitted(self, tmp_path):
        run = str(tmp_path / 'ckpt')
        with pytest.raises(M.CommitBarrierTimeout):
            save_host_shard(run, 2, 0, {'w': np.ones(2, 'f4')},
                            num_hosts=2, barrier_timeout=0.3)
        assert latest_committed_step(run) == -1

    def _check_ckpt(self, *argv):
        mod = _load_tool('check_ckpt')
        return mod.main(list(argv))

    def test_cluster_mode_clean_exits_zero(self, tmp_path, capsys):
        run = str(tmp_path / 'ckpt')
        self._save_both(run, 2)
        assert self._check_ckpt(run, '--deep', '--cluster') == 0

    def test_cluster_rank_set_mismatch_exits_7(self, tmp_path,
                                               capsys):
        """The --cluster satellite: manifest certifies process_count=3
        but only ranks {0,1} own shards -> exit 7."""
        run = str(tmp_path / 'ckpt')
        self._save_both(run, 2, tamper_meta={'process_count': 3})
        rc = self._check_ckpt(run, '--deep', '--cluster')
        assert rc == 7
        out = capsys.readouterr().out
        assert 'rank' in out.lower()

    def test_cluster_hosts_vs_process_count_disagree(self, tmp_path,
                                                     capsys):
        run = str(tmp_path / 'ckpt')
        self._save_both(run, 2, tamper_meta={'hosts': 1})
        # hosts=1 vs process_count=2: rank_set class (exit 7)
        assert self._check_ckpt(run, '--deep', '--cluster') == 7

    def test_non_cluster_deep_unchanged(self, tmp_path, capsys):
        run = str(tmp_path / 'ckpt')
        self._save_both(run, 2, tamper_meta={'process_count': 3})
        # without --cluster the rank-set audit is off: clean exit
        assert self._check_ckpt(run, '--deep') == 0


# ============================================================ plangen =======

class TestPlanGeneration:
    def test_same_seed_same_plan(self):
        a = plangen.generate_plan(7, 50, 2)
        b = plangen.generate_plan(7, 50, 2)
        assert a.to_json() == b.to_json()
        assert plangen.generate_plan(8, 50, 2).to_json() != a.to_json()

    def test_required_kinds_present_and_legal(self):
        for seed in range(12):
            plan = plangen.generate_plan(seed, 30, 2)
            kinds = [f.kind for f in plan.faults]
            for req in ('collective_hang', 'sigkill', 'torn_write'):
                assert req in kinds, (seed, kinds)
            for f in plan.faults:
                assert plangen.legal(f, 30, 2), (seed, f)

    def test_preconditions_enforced(self):
        assert not plangen.legal(Fault('sigkill', at_step=2, rank=0),
                                 30, 2)        # before first save
        assert plangen.legal(Fault('sigkill', at_step=3, rank=0),
                             30, 2)
        assert not plangen.legal(
            Fault('collective_hang', at_step=5, rank=0, delay_s=60),
            30, 1)                             # needs >1 process
        assert not plangen.legal(
            Fault('collective_hang', at_step=5, delay_s=60), 30, 2)
        assert not plangen.legal(Fault('sigkill', at_step=40, rank=0),
                                 30, 2)        # past the run
        assert not plangen.legal(Fault('nan_grads', at_step=3), 30, 2)

    def test_shrink_reaches_minimal_and_validates_oracle(self):
        plan = plangen.generate_plan(7, 50, 2)

        def oracle(p):
            kinds = [f.kind for f in p.faults]
            return 'sigkill' in kinds and 'torn_write' in kinds

        shrunk, runs = plangen.shrink(plan, oracle)
        assert sorted(f.kind for f in shrunk.faults) \
            == ['sigkill', 'torn_write']
        assert runs <= 16
        with pytest.raises(ValueError):
            plangen.shrink(plan, lambda p: False)

    def test_goldens_pin_generator_and_shrinker(self):
        """Tier-1 twin of the soak_run --smoke fixture gate: the
        committed goldens match what the code composes today."""
        with open(os.path.join(_REPO, 'tools',
                               'soak_goldens.json')) as f:
            gold = json.load(f)
        g = gold['plan_seed7']
        plan = plangen.generate_plan(7, g['steps'], g['procs'],
                                     save_every=g['save_every'],
                                     hang_s=g['hang_s'])
        assert plangen.plan_fingerprint(plan) == g['fingerprint']
        assert [f.kind for f in plan.faults] == g['kinds']
        gs = gold['shrink_demo']
        shrunk, _ = plangen.shrink(
            plan, lambda p: {'sigkill', 'torn_write'} <=
            {f.kind for f in p.faults})
        assert plangen.plan_fingerprint(shrunk) == gs['fingerprint']
        assert len(shrunk.faults) == gs['n_faults'] <= 3

    def test_emit_regression_compiles(self, tmp_path):
        plan = FaultPlan(seed=3, faults=[
            Fault('sigkill', at_step=5, rank=0)])
        path = plangen.emit_regression(
            plan, str(tmp_path / 'test_regression.py'), procs=2,
            steps=10, violations=['I6: ...'])
        import py_compile
        py_compile.compile(path, doraise=True)
        text = open(path).read()
        assert 'pytest.mark.slow' in text and 'ChaosCluster' in text


# ==================================================== invariants I6/I7 ======

@pytest.mark.faultinject
class TestSoakInvariants:
    def _ev(self, kind, step, ts):
        return {'kind': kind, 'step': step, 'ts': ts}

    def test_i6_double_publish_flagged(self, tmp_path):
        events = [self._ev('checkpoint_commit', 4, 1.0),
                  self._ev('checkpoint_commit', 4, 2.0)]
        out = check_invariants(str(tmp_path / 'none'), events=events,
                               expect_committed=False)
        assert any(v.startswith('I6') for v in out), out

    def test_i6_recommit_after_rollback_allowed(self, tmp_path):
        events = [self._ev('checkpoint_commit', 4, 1.0),
                  self._ev('checkpoint_restore', 2, 2.0),
                  self._ev('checkpoint_commit', 4, 3.0)]
        out = check_invariants(str(tmp_path / 'none'), events=events,
                               expect_committed=False)
        assert not any(v.startswith('I6') for v in out), out

    def test_i7_bad_exit_and_deadline(self, tmp_path):
        out = check_invariants(str(tmp_path / 'none'),
                               expect_committed=False, final_rc=121)
        assert any(v.startswith('I7') for v in out)
        out = check_invariants(str(tmp_path / 'none'),
                               expect_committed=False, final_rc=117)
        assert not any(v.startswith('I7') for v in out)
        out = check_invariants(str(tmp_path / 'none'),
                               expect_committed=False, final_rc=0,
                               duration_s=10.0, deadline_s=5.0)
        assert any(v.startswith('I7') for v in out)


# =================================================== run_report =============

class TestRunReportWatchdogTimeline:
    def test_watchdog_kinds_render_with_rank_attribution(
            self, tmp_path, capsys):
        rr = _load_tool('run_report')
        lines = [
            {'kind': 'steps', 'ts': 1.0, 'rank': 0, 'tag': 'soak',
             'n': 1, 'step_time_ms': [5.0]},
            {'kind': 'steps', 'ts': 1.0, 'rank': 1, 'tag': 'soak',
             'n': 1, 'step_time_ms': [5.0]},
            {'kind': 'fault_injected', 'ts': 2.0, 'rank': 1,
             'fault': 'collective_hang', 'step': 4, 'seed': 7},
            {'kind': 'straggler', 'ts': 2.2, 'rank': 0, 'peer': 1,
             'heartbeat_age_s': 3.2},
            {'kind': 'timeout', 'ts': 2.5, 'rank': 0,
             'op': 'allreduce-mean', 'budget_s': 4.0,
             'missing': [1]},
            {'kind': 'coordinated_abort', 'ts': 2.6, 'rank': 0,
             'reason': 'timeout'},
            {'kind': 'quorum_lost', 'ts': 2.7, 'rank': 0,
             'stale': [1], 'live': 1},
        ]
        p = tmp_path / 'telemetry-r0.jsonl'
        with open(p, 'w') as f:
            for rec in lines:
                f.write(json.dumps(rec) + '\n')
        events, sources, skew = rr.load_events([str(p)], [])
        report = rr.analyze(events, sources, skew)
        kinds = [(r['kind'], r['rank']) for r in report['timeline']]
        assert ('fault_injected', 1) in kinds
        assert ('timeout', 0) in kinds
        assert ('straggler', 0) in kinds
        assert ('quorum_lost', 0) in kinds
        assert ('coordinated_abort', 0) in kinds
        row = next(r for r in report['timeline']
                   if r['kind'] == 'timeout')
        assert row['op'] == 'allreduce-mean' and row['missing'] == [1]
        wd = report['watchdog']
        assert wd['timeout']['per_rank'] == {0: 1}
        assert wd['fault_injected']['per_rank'] == {1: 1}
        rr.render(report)
        out = capsys.readouterr().out
        assert 'watchdog / collective supervision' in out
        assert 'timeout' in out


# ================================================ cluster e2e (slow) ========

# slow: spins real worker interpreters; nothing automatic runs it.
# `tools/soak_run.py --smoke` makes the same spin by hand.
@pytest.mark.slow
@pytest.mark.faultinject
class TestChaosClusterE2E:
    def test_smoke_plan_cluster(self, tmp_path):
        """Folds the old single-process chaos_run driver cases into
        the 2-process topology: a hung collective (watchdog timeout ->
        coordinated abort -> elastic restart, exit 121), a SIGKILLed
        worker (crash recovery), a SIGTERM preemption (exit 117), and
        a torn manifest — invariants I1-I7 plus bit-exact final state
        on both ranks."""
        sys.path.insert(0, os.path.join(_REPO, 'tools'))
        try:
            from soak_run import SMOKE_PLAN, _final_w
        finally:
            sys.path.pop(0)
        report = ChaosCluster(
            procs=2, plan=FaultPlan.from_json(json.dumps(SMOKE_PLAN)),
            steps=12, workdir=str(tmp_path / 'cluster'),
            collective_timeout_s=5.0, barrier_timeout_s=10.0,
            watchdog='step=60,grace=2', deadline_s=180.0,
            max_restarts=6).run()
        assert report['ok'], report['violations']
        kinds = {e['fault'] for e in report['injected']}
        assert {'collective_hang', 'sigkill', 'sigterm',
                'torn_write'} <= kinds
        assert report['preempt_exit_codes'] == [117]
        assert WATCHDOG_EXIT_CODE in report['watchdog_exit_codes']
        ref = _final_w(12, world=2)
        for r, doc in report['finals'].items():
            np.testing.assert_array_equal(
                np.asarray(doc['final_w'], 'f4'), ref)

    def test_jax_distributed_clean_soak(self, tmp_path):
        """A kill-free plan with jax.distributed-initialized workers:
        the coordination service comes up, process_count reports the
        cluster, and the soak completes clean."""
        report = ChaosCluster(
            procs=2, plan=FaultPlan(seed=1, faults=[]), steps=6,
            workdir=str(tmp_path / 'cluster'),
            collective_timeout_s=20.0, watchdog='step=60,grace=2',
            deadline_s=120.0, jax_distributed=True).run()
        assert report['ok'], report['violations']
        from paddle_tpu.resilience.chaos import load_run_events
        evs = load_run_events(str(tmp_path / 'cluster'))
        metas = [e for e in evs if e.get('kind') == 'run_meta'
                 and e.get('jax_distributed')]
        assert metas and metas[0]['process_count'] == 2
