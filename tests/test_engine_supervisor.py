"""Self-healing runtime (paddle_tpu.resilience.supervisor).

The PlanSupervisor actuator closing the observe→act loop: trigger
classification and the debounce/cooldown hysteresis (one sustained
incident actuates EXACTLY once), the safety ladder's degrade-to-
incumbent rungs (planner failure, compile failure, margin not met,
swap refused — never a crash), drift-folded calibration, the
coordinated-reshape request file + elastic restart path (no
max_restarts burn), the watchdog Budget's measured-window reset after
a plan swap, the plangen supervisor-migration coverage class, and the
headline: an in-process dp=8 trainer live-migrates to a tp>1 plan
under injected all-reduce drift with exactly one plan_swap and finite
losses throughout.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.telemetry import get_recorder
from paddle_tpu.analysis import costmodel as cm
from paddle_tpu.resilience import plangen
from paddle_tpu.resilience.chaos import (
    Fault, FaultPlan, ChaosCluster, load_run_events)
from paddle_tpu.resilience.supervisor import (
    PlanSupervisor, SupervisorConfig, TrainerHost, resolve_supervisor,
    TRIGGER_POLICIES, drift_calibration, write_reshape_request,
    read_reshape_request, RESHAPE_REQUEST_NAME, SUPERVISOR_ENV)
from paddle_tpu.resilience.watchdog import Budget

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ config --------
class TestSupervisorConfig:
    def test_from_env_off(self):
        for text in (None, '', '0', 'off', 'False', 'OFF'):
            assert SupervisorConfig.from_env(text) is None

    def test_from_env_on_defaults(self):
        for text in ('1', 'on', 'true', 'ON'):
            cfg = SupervisorConfig.from_env(text)
            assert cfg is not None
            assert cfg.debounce_s == 0.25
            assert cfg.cooldown_s == 30.0
            assert cfg.margin == 0.1
            assert cfg.max_swaps is None

    def test_from_env_kv(self):
        cfg = SupervisorConfig.from_env(
            'margin=0.2,cooldown=10,debounce=1,max_swaps=2')
        assert cfg.margin == 0.2
        assert cfg.cooldown_s == 10.0
        assert cfg.debounce_s == 1.0
        assert cfg.max_swaps == 2

    def test_from_env_ignores_junk(self):
        cfg = SupervisorConfig.from_env('margin=nope,bogus=1,cooldown=5')
        assert cfg is not None and cfg.cooldown_s == 5.0
        assert cfg.margin == 0.1      # unparsable value -> default

    def test_policy_overrides(self):
        cfg = SupervisorConfig(policies={'slo_breach': None,
                                         'custom_kind': 'replan'})
        assert 'slo_breach' not in cfg.policies
        assert cfg.policies['custom_kind'] == 'replan'
        assert cfg.policies['drift_detected'] == 'replan'
        # the shared table itself is never mutated
        assert TRIGGER_POLICIES['slo_breach'] == 'replan'

    def test_resolve_posture(self, monkeypatch):
        monkeypatch.setenv(SUPERVISOR_ENV, '1')
        assert resolve_supervisor(False) is None      # explicit beats env
        assert resolve_supervisor(None) is not None   # env decides
        monkeypatch.setenv(SUPERVISOR_ENV, '0')
        assert resolve_supervisor(None) is None
        cfg = resolve_supervisor(True)
        assert isinstance(cfg, SupervisorConfig)
        assert resolve_supervisor(cfg) is cfg
        assert resolve_supervisor({'margin': 0.3}).margin == 0.3
        with pytest.raises(TypeError):
            resolve_supervisor(42)


# ------------------------------------------------- drift calibration --------
class TestDriftCalibration:
    def test_from_scratch(self):
        cal = drift_calibration(
            None, [{'op': 'all-reduce', 'us_ratio': 50.0}])
        assert cal is not None
        ent = cal.per_op['all-reduce']
        assert ent['alpha_us'] == cm.DEFAULT_LINK_LATENCY_US * 50.0
        assert ent['beta_us_per_byte'] == pytest.approx(
            50.0 / (cm.DEFAULT_LINK_BW_GBPS * 1e3))
        assert cal.meta['source'] == 'supervisor-drift'

    def test_unusable_ratio_returns_base(self):
        base = cm.Calibration(per_op={'all-gather': {'alpha_us': 2.0}})
        for incs in ([], [{'op': 'all-reduce'}],
                     [{'op': 'all-reduce', 'us_ratio': 0.5}],
                     [{'us_ratio': 9.0}]):
            assert drift_calibration(base, incs) is base
        assert drift_calibration(None, []) is None

    def test_base_scaled_and_preserved(self):
        base = cm.Calibration(
            per_op={'all-reduce': {'alpha_us': 2.0,
                                   'beta_us_per_byte': 0.001},
                    'all-gather': {'alpha_us': 3.0}},
            link_bw_gbps=45.0)
        cal = drift_calibration(
            base, [{'op': 'all-reduce', 'us_ratio': 10.0}])
        assert cal is not base
        assert cal.per_op['all-reduce']['alpha_us'] == 20.0
        assert cal.per_op['all-reduce']['beta_us_per_byte'] == 0.01
        # untouched ops and link anchors ride through unchanged
        assert cal.per_op['all-gather'] == {'alpha_us': 3.0}
        assert cal.link_bw_gbps == 45.0
        assert base.per_op['all-reduce']['alpha_us'] == 2.0


# -------------------------------------------- reshape request file ----------
class TestReshapeRequest:
    def test_roundtrip_and_seq(self, tmp_path):
        wd = str(tmp_path)
        assert read_reshape_request(wd) is None
        seq = write_reshape_request(wd, mesh={'dp': 2, 'tp': 4},
                                    env={'K': 1}, reason='drift')
        assert seq == 1
        doc = read_reshape_request(wd)
        assert doc['seq'] == 1
        assert doc['mesh'] == {'dp': 2, 'tp': 4}
        assert doc['env'] == {'K': '1'}       # env values stringified
        assert doc['reason'] == 'drift'
        # seq is monotone across writes
        assert write_reshape_request(wd, mesh={'dp': 4}) == 2
        assert read_reshape_request(wd)['mesh'] == {'dp': 4}

    def test_torn_file_reads_absent(self, tmp_path):
        path = tmp_path / RESHAPE_REQUEST_NAME
        path.write_text('{"seq": 1, "mesh')
        assert read_reshape_request(str(tmp_path)) is None
        path.write_text('[1, 2]')             # wrong shape, not torn
        assert read_reshape_request(str(tmp_path)) is None


# ----------------------------------------------- budget reset rung ----------
class TestBudgetResetMeasured:
    def test_measured_drops_to_default(self):
        b = Budget(slack=8.0)
        assert b.note_measured([0.1] * 16) is not None
        assert b.step_source == 'measured'
        assert b.reset_measured() is None
        assert b.step_source == 'default'
        assert b.step_s is None

    def test_reset_to_costmodel_estimate(self):
        b = Budget(slack=8.0)
        b.note_measured([0.1] * 16)
        new = b.reset_measured(est_step_us=2_000_000)
        assert new == pytest.approx(2.0 * 8.0)
        assert b.step_source == 'costmodel'
        # floor: tiny estimates never produce a hair-trigger deadline
        assert b.reset_measured(est_step_us=10) == 5.0

    def test_explicit_budget_is_a_contract(self):
        b = Budget(step_s=30.0)
        assert b.reset_measured(est_step_us=2_000_000) is None
        assert b.step_s == 30.0 and b.step_source == 'explicit'


# --------------------------------------------------- safety ladder ----------
class _FakePlan:
    def __init__(self, mesh, assignment='replicated', score_us=100.0):
        self.mesh_axes = dict(mesh)
        self.assignment = assignment
        self.score_us = float(score_us)


class _FakeResult:
    def __init__(self, winner, extra=None):
        self.winner = winner
        self.candidates = [winner] + list(extra or [])
        self.fallbacks = []


class FakeHost:
    """The five-method host protocol with scriptable failures."""

    def __init__(self, winner=None, extra=None, incumbent=(None, None),
                 fail=None, refuse_swap=False):
        self.winner = winner or _FakePlan({'dp': 2, 'tp': 2})
        self.extra = extra or []
        self._incumbent = incumbent
        self.fail = fail
        self.refuse_swap = refuse_swap
        self.calls = []
        self.swapped = []

    def calibration(self):
        return None

    def healthy_devices(self, incident):
        self.calls.append(('devices', incident.get('policy')))
        return [0, 1, 2, 3]

    def replan(self, devices, calibration):
        self.calls.append(('replan', len(devices)))
        if self.fail == 'plan':
            raise RuntimeError('planner exploded')
        return _FakeResult(self.winner, self.extra)

    def incumbent(self):
        return self._incumbent

    def precompile(self, plan, devices):
        self.calls.append(('compile', dict(plan.mesh_axes)))
        if self.fail == 'compile':
            raise RuntimeError('lowering failed')

    def request_swap(self, plan, devices, incident):
        self.calls.append(('swap', dict(plan.mesh_axes)))
        if self.fail == 'swap':
            raise RuntimeError('queue rejected')
        if self.refuse_swap:
            return False
        self.swapped.append(plan)
        return True


def _incident(sup, kind='drift_detected', **data):
    """Push one trigger through _handle synchronously (no thread) and
    return the terminal incident record."""
    rec = {'kind': kind}
    rec.update(data)
    sup._handle(rec)
    return sup.incidents[-1]


def _capture():
    recs = []
    hook = lambda r: recs.append(dict(r))   # noqa: E731
    get_recorder().subscribe(hook)
    return recs, hook


class TestSafetyLadder:
    CFG = dict(debounce_s=0.01, cooldown_s=0.0, margin=0.1)

    def test_swap_happy_path(self):
        host = FakeHost(winner=_FakePlan({'dp': 2, 'tp': 2},
                                         score_us=80.0),
                        incumbent=(_FakePlan({'dp': 4}), 0.5))
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        recs, hook = _capture()
        try:
            inc = _incident(sup, us_ratio=9.0, op='all-reduce')
        finally:
            get_recorder().unsubscribe(hook)
        assert inc['outcome'] == 'swap'
        assert sup.swaps == 1 and len(host.swapped) == 1
        rem = [r for r in recs if r['kind'] == 'remediation']
        assert len(rem) == 1 and rem[0]['outcome'] == 'swap'
        assert rem[0]['mesh'] == {'dp': 2, 'tp': 2}
        # ladder ran in order: devices -> replan -> compile -> swap
        assert [c[0] for c in host.calls] == ['devices', 'replan',
                                              'compile', 'swap']

    def test_backoff_policy_never_touches_host(self):
        host = FakeHost()
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        for kind in ('rank_divergence', 'quorum_lost'):
            assert _incident(sup, kind)['outcome'] == 'backoff'
        assert host.calls == [] and sup.swaps == 0

    def test_planner_failure_degrades(self):
        sup = PlanSupervisor(FakeHost(fail='plan'),
                             SupervisorConfig(**self.CFG))
        recs, hook = _capture()
        try:
            assert _incident(sup)['outcome'] == 'degraded'
        finally:
            get_recorder().unsubscribe(hook)
        rem = [r for r in recs if r['kind'] == 'remediation'][-1]
        assert rem['stage'] == 'plan' and 'planner exploded' in rem['error']

    def test_compile_failure_degrades(self):
        host = FakeHost(fail='compile')
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        recs, hook = _capture()
        try:
            assert _incident(sup)['outcome'] == 'degraded'
        finally:
            get_recorder().unsubscribe(hook)
        rem = [r for r in recs if r['kind'] == 'remediation'][-1]
        assert rem['stage'] == 'compile'
        assert host.swapped == []            # incumbent keeps running

    def test_swap_failure_degrades(self):
        sup = PlanSupervisor(FakeHost(fail='swap'),
                             SupervisorConfig(**self.CFG))
        recs, hook = _capture()
        try:
            assert _incident(sup)['outcome'] == 'degraded'
        finally:
            get_recorder().unsubscribe(hook)
        rem = [r for r in recs if r['kind'] == 'remediation'][-1]
        assert rem['stage'] == 'swap' and sup.swaps == 0

    def test_swap_refused_holds(self):
        sup = PlanSupervisor(FakeHost(refuse_swap=True),
                             SupervisorConfig(**self.CFG))
        assert _incident(sup)['outcome'] == 'hold'
        assert sup.swaps == 0

    def test_margin_gate_holds(self):
        # candidate 95us vs incumbent re-scored at 100us in the SAME
        # planner run: 5% better < the 10% margin -> hold
        incumbent = _FakePlan({'dp': 8}, score_us=100.0)
        host = FakeHost(winner=_FakePlan({'dp': 2, 'tp': 4},
                                         score_us=95.0),
                        extra=[incumbent],
                        incumbent=(incumbent, None))
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        recs, hook = _capture()
        try:
            assert _incident(sup)['outcome'] == 'hold'
        finally:
            get_recorder().unsubscribe(hook)
        rem = [r for r in recs if r['kind'] == 'remediation'][-1]
        assert rem['reason'] == 'margin not met'
        assert rem['incumbent_s'] == pytest.approx(100e-6)
        assert host.swapped == []

    def test_margin_gate_passes_live_estimate(self):
        # no re-scored incumbent in the run -> the live median step
        # (0.5s) is the bar; an 80us candidate clears any margin
        host = FakeHost(winner=_FakePlan({'dp': 2, 'tp': 2},
                                         score_us=80.0),
                        incumbent=(_FakePlan({'dp': 8}), 0.5))
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        assert _incident(sup)['outcome'] == 'swap'

    def test_winner_is_incumbent_holds(self):
        same = _FakePlan({'dp': 8}, score_us=90.0)
        host = FakeHost(winner=_FakePlan({'dp': 8}, score_us=90.0),
                        incumbent=(same, 0.5))
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        recs, hook = _capture()
        try:
            assert _incident(sup)['outcome'] == 'hold'
        finally:
            get_recorder().unsubscribe(hook)
        rem = [r for r in recs if r['kind'] == 'remediation'][-1]
        assert rem['reason'] == 'winner is the incumbent'

    def test_max_swaps_cap(self):
        host = FakeHost()
        sup = PlanSupervisor(host, SupervisorConfig(max_swaps=1,
                                                    **self.CFG))
        assert _incident(sup)['outcome'] == 'swap'
        sup._cooldown_until = 0.0
        assert _incident(sup)['outcome'] == 'hold'
        assert len(host.swapped) == 1

    def test_cooldown_suppresses_inside_window(self):
        sup = PlanSupervisor(FakeHost(), SupervisorConfig(**self.CFG))
        sup._cooldown_until = time.monotonic() + 60.0
        sup._handle({'kind': 'drift_detected'})
        assert sup.incidents == [] and sup._suppressed >= 1

    def test_exclude_rank_policy_reaches_host(self):
        host = FakeHost(winner=_FakePlan({'dp': 3}, score_us=10.0))
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        inc = _incident(sup, 'straggler_suspect', suspect=5)
        assert inc['policy'] == 'exclude_rank'
        assert ('devices', 'exclude_rank') in host.calls
        assert inc['outcome'] == 'swap'


class TestSupervisorThread:
    def test_exactly_once_under_sustained_triggers(self):
        """Six rapid triggers coalesce into ONE incident (debounce),
        three more inside the cooldown are suppressed — one swap
        total, through the real recorder subscription."""
        host = FakeHost()
        sup = PlanSupervisor(host, SupervisorConfig(
            debounce_s=0.2, cooldown_s=120.0, margin=0.0)).start()
        try:
            for _ in range(6):
                telemetry.event('drift_detected', op='all-reduce',
                                us_ratio=9.0, cause='us_ratio')
            deadline = time.time() + 10
            while time.time() < deadline and not sup.incidents:
                time.sleep(0.02)
            assert len(sup.incidents) == 1
            inc = sup.incidents[0]
            assert inc['outcome'] == 'swap'
            assert inc['triggers'] == 6
            assert inc['kinds'] == ['drift_detected']
            # sustained drift inside the cooldown: suppressed, no
            # second actuation
            for _ in range(3):
                telemetry.event('drift_detected', op='all-reduce',
                                us_ratio=9.0, cause='us_ratio')
            time.sleep(0.5)
            assert len(sup.incidents) == 1 and sup.swaps == 1
            assert len(host.swapped) == 1
        finally:
            sup.stop(timeout=2.0)

    def test_cooldown_rearm(self):
        host = FakeHost()
        sup = PlanSupervisor(host, SupervisorConfig(
            debounce_s=0.02, cooldown_s=0.2, margin=0.0)).start()
        try:
            telemetry.event('drift_detected', op='all-reduce',
                            us_ratio=9.0)
            deadline = time.time() + 10
            while time.time() < deadline and len(sup.incidents) < 1:
                time.sleep(0.02)
            time.sleep(0.4)                  # cooldown expires
            telemetry.event('drift_detected', op='all-reduce',
                            us_ratio=9.0)
            deadline = time.time() + 10
            while time.time() < deadline and len(sup.incidents) < 2:
                time.sleep(0.02)
            assert len(sup.incidents) == 2
            assert len(host.swapped) == 2
        finally:
            sup.stop(timeout=2.0)

    def test_stopped_supervisor_ignores_events(self):
        host = FakeHost()
        sup = PlanSupervisor(host, SupervisorConfig(
            debounce_s=0.01, cooldown_s=0.0)).start()
        sup.stop(timeout=2.0)
        telemetry.event('drift_detected', op='all-reduce', us_ratio=9.0)
        time.sleep(0.2)
        assert sup.incidents == [] and host.calls == []

    def test_non_trigger_kinds_filtered(self):
        sup = PlanSupervisor(FakeHost(), SupervisorConfig()).start()
        try:
            telemetry.event('step', step=1)
            telemetry.event('compile', name='x')
            time.sleep(0.1)
            assert sup._q.empty() and sup.incidents == []
        finally:
            sup.stop(timeout=2.0)


# --------------------------------------- monitor plan_swap hygiene ----------
class TestMonitorSwapReset:
    def test_slo_monitor_clears_latch(self):
        from paddle_tpu.telemetry.monitors import SLOMonitor
        mon = SLOMonitor(ttft_budget_s=1.0)
        mon._latched.add('ttft_p99')
        mon.observe({'kind': 'plan_swap'}, None)
        assert mon._latched == set()

    def test_drift_monitor_swap_grace(self):
        from paddle_tpu.telemetry.monitors import DriftMonitor
        mon = DriftMonitor()
        mon._ratios[('all-reduce', 'i0')] = [9.0]
        mon._latched.add(('all-reduce', 'i0'))
        mon.observe({'kind': 'plan_swap'}, None)
        assert mon._ratios == {} and mon._latched == set()
        # the swap's own rebuild compiles are the actuation, not drift
        assert mon._post_swap_compiles == 2
        mon.observe({'kind': 'compile', 'name': 'a'}, None)
        mon.observe({'kind': 'compile', 'name': 'b'}, None)
        assert mon._post_swap_compiles == 0
        assert mon.detections == []


# ----------------------------------------- plangen coverage class -----------
class TestPlangenSupervisorClass:
    def test_drift_legality(self):
        ok = Fault('drift', at_step=5, rank=0, op='all-reduce',
                   us_ratio=8.0)
        assert plangen.legal(ok, steps=20, procs=2)
        # the actuator lives on rank 0's recorder: drift elsewhere (or
        # unstamped) never reaches it
        assert not plangen.legal(
            Fault('drift', at_step=5, rank=1, us_ratio=8.0), 20, 2)
        assert not plangen.legal(
            Fault('drift', rank=0, us_ratio=8.0), 20, 2)
        assert 'drift' in plangen.OPTIN_KINDS
        assert 'drift' not in plangen.GENERATABLE_KINDS

    def test_supervisor_plan_composition(self):
        plan = plangen.generate_plan(11, 16, 2, n_faults=0, require=(),
                                     supervisor=True)
        kinds = [f.kind for f in plan.faults]
        assert kinds == ['drift', 'sigkill']
        drift, kill = plan.faults
        assert drift.rank == 0 and drift.op == 'all-reduce'
        assert drift.us_ratio >= 6.0
        # the mid-migration crash lands one step after the sensor edge
        assert kill.at_step == min(16, drift.at_step + 1)
        assert plan.name.endswith('+sup')
        for f in plan.faults:
            assert plangen.legal(f, 16, 2)
        # purity: same knobs, same plan
        again = plangen.generate_plan(11, 16, 2, n_faults=0, require=(),
                                      supervisor=True)
        assert plan.to_json() == again.to_json()

    def test_default_pool_never_draws_drift(self):
        for seed in range(6):
            plan = plangen.generate_plan(seed, 30, 2, n_faults=8)
            assert 'drift' not in [f.kind for f in plan.faults]
            assert not plan.name.endswith('+sup')

    def test_golden_fingerprint_unchanged(self):
        """The opt-in class must not shift pre-existing seeded draw
        streams: the pinned seed-7 golden still composes byte-for-
        byte."""
        with open(os.path.join(_REPO, 'tools',
                               'soak_goldens.json')) as f:
            g = json.load(f)['plan_seed7']
        plan = plangen.generate_plan(7, g['steps'], g['procs'],
                                     save_every=g['save_every'],
                                     hang_s=g['hang_s'])
        assert plangen.plan_fingerprint(plan) == g['fingerprint']


# ---------------------------------------- elastic coordinated reshape -------
class TestCoordinatedReshape:
    def test_request_reshape_restarts_all_without_budget_burn(
            self, tmp_path):
        """A reshape_request.json appearing in the watched dir
        restarts EVERY worker together with the request's env merged
        in — reshapes counted on their own budget, max_restarts and
        the crash backoff untouched."""
        from paddle_tpu.distributed import elastic
        wd = str(tmp_path)
        marker = str(tmp_path / 'marks.jsonl')
        code = (
            "import json, os, time\n"
            "with open(%r, 'a') as f:\n"
            "    f.write(json.dumps({\n"
            "        'rank': os.environ['PADDLE_TRAINER_ID'],\n"
            "        'reshapes': os.environ.get(\n"
            "            'PADDLE_ELASTIC_RESHAPE_COUNT', '0'),\n"
            "        'mesh': os.environ.get(\n"
            "            'PADDLE_TPU_RESHAPE_MESH'),\n"
            "        'tag': os.environ.get('NEW_PLAN_TAG')}) + '\\n')\n"
            "time.sleep(300)\n" % marker)
        procs = elastic.start_local_trainers(
            [[sys.executable, '-c', code]] * 2)
        events = []
        th = threading.Thread(
            target=elastic.watch_local_trainers, args=(procs,),
            kwargs=dict(max_restarts=0, poll=0.05, reshape_dir=wd,
                        deadline=60.0,
                        on_event=lambda k, t: events.append(
                            (k, t.rank))),
            daemon=True)
        th.start()
        try:
            def lines():
                try:
                    with open(marker) as f:
                        return [json.loads(x) for x in f
                                if x.strip()]
                except FileNotFoundError:
                    return []

            deadline = time.time() + 20
            while time.time() < deadline and len(lines()) < 2:
                time.sleep(0.05)
            assert len(lines()) == 2, 'workers never came up'
            seq = elastic.request_reshape(
                wd, mesh={'dp': 2}, env={'NEW_PLAN_TAG': 'v2'},
                reason='test-drift')
            assert seq == 1
            deadline = time.time() + 30
            while time.time() < deadline and len(lines()) < 4:
                time.sleep(0.05)
            rows = lines()
            assert len(rows) == 4, rows
            gen2 = [r for r in rows if r['reshapes'] == '1']
            assert len(gen2) == 2
            assert {r['rank'] for r in gen2} == {'0', '1'}
            for r in gen2:
                assert r['mesh'] == 'dp=2'
                assert r['tag'] == 'v2'
            assert events.count(('reshape', 0)) == 1
            assert events.count(('reshape', 1)) == 1
            for p in procs:
                assert p.reshapes == 1
                assert p.restarts == 0 and p.preemptions == 0
            # the watch loop latches the seq: the same request never
            # fires twice
            time.sleep(0.5)
            assert len(lines()) == 4
        finally:
            elastic.terminate_local_procs(procs, grace=2.0)
            th.join(15)


# ----------------------------------- in-process live migration (headline) ---
class TestLiveMigration:
    @staticmethod
    def _armed_dp8_trainer():
        from paddle_tpu import distributed as dist
        from paddle_tpu.parallel import ParallelTrainer
        dist.init_parallel_env(axes={'dp': 8})
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(64, 256), nn.ReLU(),
                            nn.Linear(256, 64))
        opt = paddle.optimizer.Momentum(
            learning_rate=0.01, parameters=net.parameters())
        tr = ParallelTrainer(
            net, opt, lambda out, y: ((out - y) ** 2).mean(),
            supervisor={'debounce_s': 0.05, 'cooldown_s': 120.0,
                        'margin': 0.0})
        rs = np.random.RandomState(1)
        return (tr, rs.randn(16, 64).astype('float32'),
                rs.randn(16, 64).astype('float32'))

    def test_dp8_migrates_under_injected_drift(self):
        """The tentpole end-to-end, in one process: a dp=8 trainer
        under 50x all-reduce drift re-plans onto a tp>1 layout, swaps
        at a step boundary with exactly one plan_swap, keeps the loss
        finite, and holds through the cooldown."""
        from paddle_tpu.distributed import env as dist_env
        if jax.device_count() < 8:
            pytest.skip('needs 8 devices')
        recs, hook = _capture()
        tr = None
        try:
            tr, X, Y = self._armed_dp8_trainer()
            for _ in range(3):
                tr.step(X, Y)
            assert tr._supervisor is not None
            assert dict(tr.mesh.shape) == {'dp': 8}
            telemetry.event('drift_detected', cause='us_ratio',
                            op='all-reduce', instr='test',
                            us_ratio=50.0, band=4.0, windows=8)
            deadline = time.time() + 90
            while time.time() < deadline \
                    and not tr._supervisor.incidents:
                time.sleep(0.05)
            assert tr._supervisor.incidents, 'supervisor never acted'
            inc = tr._supervisor.incidents[0]
            assert inc['outcome'] == 'swap', inc
            # boundary application: the queued plan lands on the next
            # step, not mid-flight
            l1 = float(np.asarray(tr.step(X, Y)))
            shape = dict(tr.mesh.shape)
            assert shape != {'dp': 8}
            assert shape.get('tp', 1) > 1, shape
            assert int(np.prod(list(shape.values()))) == 8
            l2 = float(np.asarray(tr.step(X, Y)))
            assert np.isfinite(l1) and np.isfinite(l2)
            # sustained drift inside the cooldown: exactly-once holds
            for _ in range(3):
                telemetry.event('drift_detected', cause='us_ratio',
                                op='all-reduce', instr='test',
                                us_ratio=50.0)
            time.sleep(0.4)
            tr.step(X, Y)
            swaps = [r for r in recs if r['kind'] == 'plan_swap']
            assert len(swaps) == 1, swaps
            assert swaps[0]['trigger'] == 'drift_detected'
            rems = [r for r in recs if r['kind'] == 'remediation']
            assert [r['outcome'] for r in rems] == ['swap']
            assert tr._supervisor.swaps == 1
        finally:
            get_recorder().unsubscribe(hook)
            if tr is not None:
                tr.stop_supervisor()
            from paddle_tpu.distributed import env as dist_env
            dist_env.set_mesh(None)

    def test_armed_clean_run_never_actuates(self):
        """The twin of the drift run: the same armed trainer with NO
        trigger takes its steps (compile, step and flush events
        included) without an incident, a plan_swap or a remediation,
        and keeps its mesh."""
        from paddle_tpu.distributed import env as dist_env
        if jax.device_count() < 8:
            pytest.skip('needs 8 devices')
        recs, hook = _capture()
        tr = None
        try:
            tr, X, Y = self._armed_dp8_trainer()
            for _ in range(8):
                tr.step(X, Y)
            assert tr._supervisor is not None
            time.sleep(0.2)                 # past the debounce
            assert tr._supervisor.incidents == []
            assert tr._supervisor.swaps == 0
            assert not [r for r in recs
                        if r['kind'] in ('plan_swap', 'remediation')]
            assert dict(tr.mesh.shape) == {'dp': 8}
        finally:
            get_recorder().unsubscribe(hook)
            if tr is not None:
                tr.stop_supervisor()
            dist_env.set_mesh(None)

    def test_default_posture_is_off(self):
        """No supervisor kwarg + the conftest env pin: a trainer never
        arms the actuator by accident."""
        from paddle_tpu import distributed as dist
        from paddle_tpu.distributed import env as dist_env
        from paddle_tpu.parallel import ParallelTrainer
        if jax.device_count() < 8:
            pytest.skip('needs 8 devices')
        try:
            dist.init_parallel_env(axes={'dp': 8})
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 8))
            opt = paddle.optimizer.Momentum(
                learning_rate=0.01, parameters=net.parameters())
            tr = ParallelTrainer(net, opt,
                                 lambda o, y: ((o - y) ** 2).mean())
            X = np.zeros((8, 8), 'float32')
            tr.step(X, X)
            assert tr._supervisor is None
            # explicit False beats an armed env
            os.environ[SUPERVISOR_ENV] = '1'
            try:
                net2 = nn.Sequential(nn.Linear(8, 8))
                opt2 = paddle.optimizer.Momentum(
                    learning_rate=0.01, parameters=net2.parameters())
                tr2 = ParallelTrainer(
                    net2, opt2, lambda o, y: ((o - y) ** 2).mean(),
                    supervisor=False)
                tr2.step(X, X)
                assert tr2._supervisor is None
            finally:
                os.environ[SUPERVISOR_ENV] = '0'
        finally:
            dist_env.set_mesh(None)


# ------------------------------------------ cluster e2e (slow) --------------
@pytest.mark.slow
@pytest.mark.faultinject
class TestSupervisorChaosE2E:
    def _final_w(self, steps, world):
        sys.path.insert(0, os.path.join(_REPO, 'tools'))
        try:
            from soak_run import _final_w
        finally:
            sys.path.pop(0)
        return _final_w(steps, world=world)

    def test_drift_migrates_cluster_exactly_once(self, tmp_path):
        """Injected drift on rank 0 -> the armed supervisor writes ONE
        reshape request -> the elastic watch coordinately restarts the
        whole cluster once, on the reshape budget (zero failure
        restarts) — invariants hold and finals stay bit-exact."""
        plan = FaultPlan(seed=0, faults=[
            Fault('drift', at_step=5, rank=0, op='all-reduce',
                  us_ratio=9.0),
            # a barrier stall right after the sensor edge keeps the
            # cluster alive long enough for the actuation window
            Fault('slow_rank', at_step=6, rank=0, delay_s=0.8),
            Fault('slow_rank', at_step=9, rank=1, delay_s=0.8),
        ])
        report = ChaosCluster(
            procs=2, plan=plan, steps=16,
            workdir=str(tmp_path / 'cluster'),
            collective_timeout_s=20.0, watchdog='step=60,grace=2',
            supervisor='debounce=0.05,cooldown=120',
            deadline_s=180.0).run()
        assert report['ok'], report['violations']
        assert report['reshapes'] == {0: 1, 1: 1}
        assert report['failure_restarts'] == {0: 0, 1: 0}
        assert ('reshape', 0) in report['supervisor_events']
        assert ('reshape', 1) in report['supervisor_events']
        evs = load_run_events(report['workdir'])
        assert [e for e in evs if e.get('kind') == 'drift_detected']
        swaps = [e for e in evs if e.get('kind') == 'plan_swap']
        assert len(swaps) == 1, swaps
        assert swaps[0]['trigger'] == 'drift_detected'
        ref = self._final_w(16, world=2)
        for r, doc in report['finals'].items():
            np.testing.assert_array_equal(
                np.asarray(doc['final_w'], 'f4'), ref)

    def test_sigkill_mid_migration_is_safe(self, tmp_path):
        """The plangen '+sup' coverage class: a SIGKILL one step after
        the drift edge, i.e. racing the coordinated restart.  The
        guarantee is SAFETY — at most one actuation (the request file
        is the durable ledger), invariants I1-I7, bit-exact finals —
        whichever side of the race the kill lands on."""
        plan = plangen.generate_plan(11, 16, 2, n_faults=0, require=(),
                                     supervisor=True)
        report = ChaosCluster(
            procs=2, plan=plan, steps=16,
            workdir=str(tmp_path / 'cluster'),
            collective_timeout_s=20.0, watchdog='step=60,grace=2',
            supervisor='debounce=0.05,cooldown=120',
            deadline_s=180.0, max_restarts=6).run()
        assert report['ok'], report['violations']
        swaps = [e for e in load_run_events(report['workdir'])
                 if e.get('kind') == 'plan_swap']
        assert len(swaps) <= 1, swaps
        # a coordinated restart is all-or-nothing: every rank reshaped
        # the same number of times (0 if the kill won the race)
        assert len(set(report['reshapes'].values())) == 1
        ref = self._final_w(16, world=2)
        for r, doc in report['finals'].items():
            np.testing.assert_array_equal(
                np.asarray(doc['final_w'], 'f4'), ref)
