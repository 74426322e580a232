"""Memory observatory (paddle_tpu.telemetry.memory + friends).

Three-source HBM truth: predicted (PR-4 liveness walk) vs compiled
(XLA memory_analysis) vs live (sampler census), the per-module
``memory_compiled`` join, the latched ``MemoryMonitor`` ->
``memory_pressure`` edge, the supervisor's tightened-budget re-plan,
and the run_report ``memory`` section.

Goldens below pin the liveness estimate against XLA's own
``memory_analysis`` for the four analysis targets — measured on this
jax/XLA CPU build: lenet x0.92, gpt x0.94, widedeep x0.92,
gptserve x0.74 (entry-local liveness undercounts fusion temps most on
the paged-attention decode step).  The band is deliberately loose
([0.5, 1.3]) so an XLA upgrade shifts, not breaks, it — drift OUTSIDE
the band means one of the two sides changed meaning.

NOTE this file must sort alphabetically before test_host_embedding.py:
the seed's tier-1 run aborts there (XLA compiler crash) and later
files never execute.
"""
import json
import os
import sys

import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import telemetry
from paddle_tpu.telemetry import memory as mem
from paddle_tpu.telemetry.memory import (
    MemConfig, MemorySampler, resolve_memstats)
from paddle_tpu.telemetry.monitors import MemoryMonitor
from paddle_tpu.telemetry.recorder import EVENT_KINDS, get_recorder
from paddle_tpu.resilience.supervisor import (
    PlanSupervisor, SupervisorConfig, TRIGGER_POLICIES,
    memory_budget_hint)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_memory_state(monkeypatch):
    """Virgin recorder + module registry + no ambient sampler, and the
    env pinned off (conftest setdefaults it, but a dev shell may have
    armed it)."""
    monkeypatch.setenv(mem.MEMSTATS_ENV, '0')
    telemetry.disable()
    telemetry.reset()
    mem.reset_modules()
    mem.stop_sampler()
    yield
    mem.stop_sampler()
    mem.reset_modules()
    telemetry.disable()
    telemetry.reset()


def _tiny_compiled():
    f = jax.jit(lambda x: (x @ x.T).sum())
    return f.lower(jnp.ones((16, 16), jnp.float32)).compile()


# ------------------------------------------------------- posture ----
class TestPosture:
    def test_env_off_grammar(self):
        for text in (None, '', '0', 'off', 'false', 'no', 'OFF'):
            assert MemConfig.from_env(text) is None

    def test_env_on_defaults(self):
        for text in ('1', 'on', 'true', 'yes'):
            cfg = MemConfig.from_env(text)
            assert cfg is not None
            assert cfg.interval_s == 10.0 and cfg.budget_gb is None

    def test_env_kv_grammar(self):
        cfg = MemConfig.from_env(
            'interval=2,budget_gb=16,watermark=0.8,rearm=0.5')
        assert cfg.interval_s == 2.0
        assert cfg.budget_gb == 16.0
        assert cfg.budget_bytes == 16 * (1 << 30)
        assert cfg.watermark == 0.8 and cfg.rearm_frac == 0.5

    def test_env_kv_ignores_junk(self):
        cfg = MemConfig.from_env('budget=4,bogus=9,watermark=nope')
        assert cfg.budget_gb == 4.0 and cfg.watermark == 0.9

    def test_resolve_explicit_false_beats_env(self, monkeypatch):
        monkeypatch.setenv(mem.MEMSTATS_ENV, '1')
        assert resolve_memstats(False) is None
        assert resolve_memstats(None) is not None
        assert mem.armed() and not mem.armed(False)

    def test_resolve_passthrough(self):
        cfg = MemConfig(budget_gb=2)
        assert resolve_memstats(cfg) is cfg
        assert resolve_memstats({'budget_gb': 2}).budget_gb == 2.0
        assert resolve_memstats(True).budget_gb is None
        with pytest.raises(TypeError):
            resolve_memstats(42)

    def test_kinds_declared(self):
        for kind in ('memory_compiled', 'memory_sample',
                     'memory_pressure'):
            assert kind in EVENT_KINDS
        assert TRIGGER_POLICIES['memory_pressure'] == 'replan'


# ------------------------------------------------ compiled truth ----
class TestCompiledTruth:
    def test_note_compiled_emits_and_registers(self):
        data = mem.note_compiled('tiny', _tiny_compiled(),
                                 source='test')
        assert data is not None
        assert data['compiled_peak_bytes'] > 0
        assert data['predicted_peak_bytes'] > 0
        assert 0 < data['ratio'] < 10
        evs = telemetry.events('memory_compiled')
        assert len(evs) == 1 and evs[0]['name'] == 'tiny'
        assert evs[0]['source'] == 'test'
        # registry row behind /memory.json (newest wins)
        snap = mem.snapshot()
        assert snap['modules']['tiny']['compiled_peak_bytes'] \
            == data['compiled_peak_bytes']

    def test_note_compiled_never_raises(self):
        class Broken:
            def memory_analysis(self):
                raise RuntimeError('no backend')
        assert mem.note_compiled('x', Broken()) is None
        assert telemetry.events('memory_compiled') == []

    def test_maybe_note_compiled_off_by_default(self):
        jitted = jax.jit(lambda x: x + 1)
        out = mem.maybe_note_compiled('off', jitted,
                                      (jnp.ones((2,)),))
        assert out is None and telemetry.events('memory_compiled') == []

    def test_maybe_note_compiled_armed(self):
        jitted = jax.jit(lambda x: x * 2)
        out = mem.maybe_note_compiled('armed', jitted,
                                      (jnp.ones((4,)),),
                                      memstats=True)
        assert out is not None and out['source'] == 'armed'
        assert telemetry.events('memory_compiled')[0]['name'] == 'armed'

    def test_peak_memory_report_contributors(self):
        from paddle_tpu.analysis import hlo
        compiled = _tiny_compiled()
        module = hlo.parse_module(compiled.as_text())
        rep = hlo.peak_memory_report(module, top=64)
        # entry-local walk: a floor of the full estimate (which
        # additionally stacks callee transients), never above it
        assert 0 < rep['peak_bytes'] <= hlo.peak_memory(module)
        contribs = rep['contributors']
        assert contribs, 'peak instant must have live buffers'
        # contributors are the live set at the peak: they sum to it
        assert sum(c['bytes'] for c in contribs) == rep['peak_bytes']
        assert all(c['bytes'] > 0 for c in contribs)
        # sorted biggest-first, parameter row labelled
        sizes = [c['bytes'] for c in contribs]
        assert sizes == sorted(sizes, reverse=True)
        assert rep['param_bytes'] >= 0 and rep['at_instr']


# ---------------------------------- predicted-vs-compiled goldens ----
class TestPredictedVsCompiledGoldens:
    """The acceptance goldens: for each analysis target, the PR-4
    liveness estimate over the compiled module's own HLO must land
    within a stated band of XLA's memory_analysis reservation."""

    BAND = (0.5, 1.3)

    @pytest.mark.parametrize('target', ['lenet', 'gpt', 'widedeep',
                                        'gptserve'])
    def test_target_ratio_in_band(self, target):
        from paddle_tpu.analysis.targets import TARGETS, surrogate_step
        model, batch = TARGETS[target](None)
        params, buffers = model.functional_state()
        step = surrogate_step(model)
        compiled = jax.jit(step).lower(
            params, buffers, jax.random.PRNGKey(0), *batch).compile()
        data = mem.note_compiled(target, compiled, source='golden')
        assert data is not None, \
            f'{target}: memory_analysis unavailable on this backend'
        lo, hi = self.BAND
        assert lo <= data['ratio'] <= hi, (
            f'{target}: predicted {data["predicted_peak_bytes"]} vs '
            f'compiled {data["compiled_peak_bytes"]} -> '
            f'x{data["ratio"]} outside [{lo}, {hi}] — the liveness '
            'walk or XLA packing changed meaning')


# ---------------------------------------------------- live truth ----
class TestLiveTruth:
    def test_host_rss(self):
        rss = mem.host_rss_bytes()
        assert rss is not None and rss > 1 << 20

    def test_device_stats_absent_on_cpu(self):
        # CPU devices return no memory_stats — the documented reason
        # the sampler needs the census fallback at all
        assert mem.device_memory_stats() is None

    def test_live_arrays_census_counts_bytes(self):
        before = mem.live_arrays_bytes()
        keep = jnp.ones((1024, 256), jnp.float32)  # 1 MiB
        keep.block_until_ready()
        after = mem.live_arrays_bytes()
        assert after - before >= keep.nbytes
        del keep

    def test_sampler_once_emits_and_gauges(self):
        s = MemorySampler(MemConfig(budget_gb=1))
        sample = s.sample_once()
        assert sample is not None
        assert sample['source'] == 'live_arrays'     # CPU fallback
        assert sample['budget_bytes'] == 1 << 30
        evs = telemetry.events('memory_sample')
        assert len(evs) == 1
        gauges = get_recorder().gauges
        assert gauges.get('memory.device_bytes') == \
            sample['device_bytes']
        assert gauges.get('memory.host_rss') == sample['host_rss']
        assert s.samples == 1

    def test_sampler_peak_is_monotonic_on_census(self):
        s = MemorySampler(MemConfig())
        keep = jnp.ones((2048, 256), jnp.float32)
        keep.block_until_ready()
        first = s.sample_once()
        del keep
        second = s.sample_once()
        assert second['device_peak_bytes'] >= first['device_bytes']

    def test_ensure_sampler_posture(self):
        assert mem.ensure_sampler() is None          # env pinned off
        s = mem.ensure_sampler({'interval_s': 60})
        try:
            assert s is not None
            assert mem.ensure_sampler(True) is s     # idempotent
        finally:
            assert mem.stop_sampler() is s

    def test_snapshot_shape(self):
        mem.note_compiled('snap', _tiny_compiled())
        MemorySampler(MemConfig()).sample_once()
        doc = mem.snapshot()
        assert set(doc) >= {'modules', 'live', 'kv_pool', 'armed'}
        assert 'snap' in doc['modules']
        assert doc['live'].get('device_bytes') is not None
        assert doc['armed'] is False
        json.dumps(doc)                              # plain scalars

    def test_prometheus_families(self):
        mem.note_compiled('prom', _tiny_compiled())
        MemorySampler(MemConfig()).sample_once()
        text = mem.prometheus()
        assert 'paddle_tpu_memory_device_bytes' in text
        assert 'module="prom"' in text


# ------------------------------------------------- memory.json ------
class TestHttpdRoute:
    def test_memory_json_served(self):
        from paddle_tpu.telemetry.httpd import MetricsServer
        from urllib.request import urlopen
        mem.note_compiled('served', _tiny_compiled())
        with MetricsServer(None, port=0) as srv:
            doc = json.load(urlopen(f'{srv.url}/memory.json',
                                    timeout=5))
            assert 'served' in doc['modules']
            routes = json.load(urlopen(f'{srv.url}/',
                                       timeout=5))['routes']
            assert '/memory.json' in routes


# ------------------------------------------------ pressure edge -----
def _sample(bytes_, peak=None):
    return {'kind': 'memory_sample', 'device_bytes': bytes_,
            'device_peak_bytes': peak or bytes_,
            'source': 'live_arrays'}


class TestMemoryMonitor:
    def test_fires_exactly_once(self):
        m = MemoryMonitor(budget_bytes=1000)         # threshold 900
        m.observe(_sample(950), None)
        m.observe(_sample(980), None)
        m.observe(_sample(999), None)
        evs = telemetry.events('memory_pressure')
        assert len(evs) == 1 and len(m.breaches) == 1
        ev = evs[0]
        assert ev['observed_bytes'] == 950
        assert ev['budget_bytes'] == 1000
        assert ev['frac'] == 0.95
        assert ev['source'] == 'live_arrays'

    def test_hysteresis_rearm(self):
        m = MemoryMonitor(budget_bytes=1000)  # fire >900, re-arm <=630
        m.observe(_sample(950), None)
        m.observe(_sample(800), None)                # not low enough
        m.observe(_sample(950), None)                # still latched
        assert len(m.breaches) == 1
        m.observe(_sample(600), None)                # re-arms
        m.observe(_sample(950), None)                # fresh edge
        assert len(m.breaches) == 2

    def test_plan_swap_rearms(self):
        m = MemoryMonitor(budget_bytes=1000)
        m.observe(_sample(950), None)
        m.observe({'kind': 'plan_swap'}, None)
        m.observe(_sample(950), None)
        assert len(m.breaches) == 2

    def test_dormant_without_budget(self):
        m = MemoryMonitor()
        m.observe(_sample(10 ** 12), None)
        assert m.breaches == []
        assert telemetry.events('memory_pressure') == []

    def test_config_fills_defaults(self):
        m = MemoryMonitor(config=MemConfig(budget_gb=1,
                                           watermark=0.5,
                                           rearm_frac=0.1))
        assert m.budget_bytes == 1 << 30
        assert m.watermark == 0.5 and m.rearm_frac == 0.1


# ------------------------------------- supervisor actuation ---------
class _MemHost:
    """Minimal five-method host whose replan RECEIVES the tightened
    budget (the new 3-arg protocol)."""

    class _Plan:
        mesh_axes = {'dp': 4}
        assignment = 'replicated'
        score_us = 50.0

    def __init__(self):
        self.replans = []
        self.swapped = []

    def calibration(self):
        return None

    def healthy_devices(self, incident):
        return [0, 1, 2, 3]

    def replan(self, devices, calibration, hbm_budget_gb=None):
        self.replans.append(hbm_budget_gb)

        class R:
            winner = self._Plan()
            candidates = [winner]
            fallbacks = []
        return R()

    def incumbent(self):
        return None, None

    def precompile(self, plan, devices):
        pass

    def request_swap(self, plan, devices, incident):
        self.swapped.append(plan)
        return True


class _LegacyHost(_MemHost):
    """The classic 2-arg replan — the tightened kwarg must degrade to
    a plain re-plan, not a 'degraded' terminal."""

    def replan(self, devices, calibration):
        self.replans.append('2-arg')

        class R:
            winner = self._Plan()
            candidates = [winner]
            fallbacks = []
        return R()


class TestSupervisorActuation:
    CFG = dict(debounce_s=0.01, cooldown_s=0.0, margin=0.1)

    def _fire(self, host):
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        sup._handle({'kind': 'memory_pressure',
                     'observed_bytes': int(1.5 * (1 << 30)),
                     'budget_bytes': 1 << 30,
                     'watermark': 0.9, 'frac': 1.5})
        return sup.incidents[-1]

    def test_budget_hint_math(self):
        gib = 1 << 30
        # overshoot x1.5 -> 1 GiB * (1/1.5) * 0.9 = 0.6 GiB
        hint = memory_budget_hint([
            {'observed_bytes': int(1.5 * gib), 'budget_bytes': gib}])
        assert hint == pytest.approx(0.6)
        # under budget: only the safety margin tightens
        hint = memory_budget_hint([
            {'observed_bytes': gib // 2, 'budget_bytes': gib}])
        assert hint == pytest.approx(0.9)
        # min over incidents; rows without the numbers are skipped
        hint = memory_budget_hint([
            {'observed_bytes': int(1.5 * gib), 'budget_bytes': gib},
            {'observed_bytes': 2 * gib, 'budget_bytes': gib},
            {'other': 1}])
        assert hint == pytest.approx(0.45)
        assert memory_budget_hint([{}, {'observed_bytes': 5}]) is None

    def test_replan_receives_tightened_budget(self):
        host = _MemHost()
        inc = self._fire(host)
        assert inc['outcome'] == 'swap'
        assert host.replans == [pytest.approx(0.6)]
        assert inc['hbm_budget_gb'] == pytest.approx(0.6)
        # the terminal remediation row carries the tightened budget
        evs = telemetry.events('remediation')
        assert evs and evs[-1]['hbm_budget_gb'] == \
            pytest.approx(0.6)

    def test_legacy_2arg_host_still_replans(self):
        host = _LegacyHost()
        inc = self._fire(host)
        assert inc['outcome'] == 'swap'
        assert host.replans == ['2-arg']

    def test_pressure_without_numbers_plain_replan(self):
        host = _MemHost()
        sup = PlanSupervisor(host, SupervisorConfig(**self.CFG))
        sup._handle({'kind': 'memory_pressure'})
        assert sup.incidents[-1]['outcome'] == 'swap'
        assert host.replans == [None]      # 3-arg host, no hint


# ------------------------------------- the observatory armed, e2e ---
class TestArmedEndToEnd:
    """PADDLE_TPU_MEMSTATS armed on a dp=8 mesh: every compile choke
    point reports, the sampler reads no device value, and one breach
    tightens exactly one re-plan."""

    def test_every_module_reports_and_sampler_adds_no_syncs(
            self, monkeypatch):
        import numpy as np
        from jax.sharding import Mesh
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.parallel import ParallelTrainer
        monkeypatch.setenv(mem.MEMSTATS_ENV, 'interval=3600')
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                            nn.Linear(32, 4))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ('dp',))
        tr = ParallelTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)
        rs = np.random.RandomState(0)
        x = rs.randn(16, 16).astype('float32')
        y = rs.randn(16, 4).astype('float32')
        tr.step(x, y)
        tr.compiled_text()
        paddle.seed(1)
        m2 = paddle.hapi.Model(nn.Linear(8, 2))
        m2.prepare(optimizer=paddle.optimizer.SGD(
            learning_rate=0.1, parameters=m2.network.parameters()),
            loss=nn.MSELoss())
        m2.train_batch(rs.randn(4, 8).astype('float32'),
                       rs.randn(4, 2).astype('float32'))
        noted = {e['name'] for e in telemetry.events('memory_compiled')}
        assert {'ParallelTrainer.step', 'Model.train_batch'} <= noted
        sampler = mem.ensure_sampler()
        assert sampler is not None
        with jax.transfer_guard_device_to_host('disallow'):
            for _ in range(8):
                tr.step(x, y)
                sample = sampler.sample_once()
        assert sample['source'] == 'live_arrays'

    def test_near_budget_allocation_tightens_one_replan(self):
        """Sampler -> MemoryMonitor -> PlanSupervisor, chained: the
        census sits just under the watermark, one seeded allocation
        crosses it, the latch holds on the next sample."""
        import time
        from paddle_tpu.telemetry import LiveAggregator
        agg = LiveAggregator().install()
        host = _MemHost()
        sup = PlanSupervisor(host, SupervisorConfig(
            debounce_s=0.01, cooldown_s=0.0, margin=0.1)).start()
        try:
            census = mem.live_arrays_bytes() or 0
            budget = int((census + (4 << 20)) / 0.9)
            budget_gb = budget / float(1 << 30)
            agg.attach_monitor(MemoryMonitor(budget_bytes=budget))
            probe = MemorySampler(MemConfig(budget_gb=budget_gb))
            probe.sample_once()         # below the watermark
            assert telemetry.events('memory_pressure') == []
            ballast = jnp.ones((2 << 20,), jnp.float32)
            ballast.block_until_ready()     # 8 MiB: twice the headroom
            probe.sample_once()         # crosses: THE edge
            probe.sample_once()         # latched
            deadline = time.time() + 10
            while time.time() < deadline and not sup.incidents:
                time.sleep(0.05)
            del ballast
            assert len(telemetry.events('memory_pressure')) == 1
            assert [i['outcome'] for i in sup.incidents] == ['swap']
            assert len(host.replans) == 1
            assert host.replans[0] is not None
            assert host.replans[0] < budget_gb
        finally:
            sup.stop()
            agg.uninstall()


# ------------------------------------------- run_report section -----
def _run_report_mod():
    sys.path.insert(0, os.path.join(_REPO, 'tools'))
    try:
        import run_report
    finally:
        sys.path.pop(0)
    return run_report


class TestRunReportMemory:
    def _write(self, tmp_path, rows):
        p = tmp_path / 'telemetry-r0.jsonl'
        with open(p, 'w') as f:
            for i, r in enumerate(rows):
                r = dict(r, ts=1000.0 + i, t=float(i), rank=0)
                f.write(json.dumps(r) + '\n')
        return tmp_path

    def test_memory_section_three_way(self, tmp_path):
        rr = _run_report_mod()
        d = self._write(tmp_path, [
            {'kind': 'memory_compiled', 'name': 'step',
             'source': 'trainer-hlo', 'predicted_peak_bytes': 900,
             'compiled_peak_bytes': 1000, 'ratio': 0.9,
             'argument_bytes': 400, 'output_bytes': 100,
             'temp_bytes': 500, 'alias_bytes': 0, 'code_bytes': 7},
            {'kind': 'memory_compiled', 'name': 'serve',
             'source': 'serving', 'predicted_peak_bytes': 550,
             'compiled_peak_bytes': 500, 'ratio': 1.1},
            {'kind': 'memory_sample', 'source': 'live_arrays',
             'device_bytes': 800, 'device_peak_bytes': 900,
             'host_rss': 4096, 'budget_bytes': 1000},
            {'kind': 'memory_pressure', 'observed_bytes': 950,
             'budget_bytes': 1000, 'watermark': 0.9, 'frac': 0.95,
             'source': 'live_arrays'},
        ])
        events, sources, skew = rr.load_events(
            rr.discover([str(d)])[0], [])
        rep = rr.analyze(events, sources, skew)
        memsec = rep['memory']
        assert set(memsec['modules']) == {'step', 'serve'}
        assert memsec['modules']['step']['ratio'] == 0.9
        assert memsec['ratio_mean'] == pytest.approx(1.0)
        assert memsec['live']['device_bytes'] == 800
        assert memsec['live']['samples'] == 1
        assert memsec['pressure_events'] == 1
        # memory_pressure lands on the resilience timeline with its
        # numbers intact
        rows = [r for r in rep['timeline']
                if r['kind'] == 'memory_pressure']
        assert rows and rows[0]['observed_bytes'] == 950
        assert rows[0]['budget_bytes'] == 1000
        # and the human renderer prints the section
        import io
        buf = io.StringIO()
        rr.render(rep, stream=buf)
        text = buf.getvalue()
        assert '-- memory (predicted vs compiled vs live) --' in text
        assert 'MEMORY PRESSURE' in text

    def test_memory_section_absent_when_no_events(self, tmp_path):
        rr = _run_report_mod()
        d = self._write(tmp_path, [
            {'kind': 'compile', 'name': 'x', 'dur_s': 0.1}])
        events, sources, skew = rr.load_events(
            rr.discover([str(d)])[0], [])
        assert rr.analyze(events, sources, skew)['memory'] is None


# ------------------------------------- engine/cluster surfaces ------
class TestSurfaces:
    def test_kv_frag_in_live_gauges(self):
        from paddle_tpu.telemetry.live import LiveAggregator
        agg = LiveAggregator()
        agg.write({'kind': 'serve_step', 'live': 1, 'batch': 1,
                   'span': 2, 'decoded': 2, 'queued': 0,
                   'kv_frag_frac': 0.25, 'kv_largest_free_run': 6,
                   'kv_high_water': 3})
        gauges = agg.snapshot()['serving']['gauges']
        assert gauges['kv_frag_frac'] == 0.25
        assert gauges['kv_high_water'] == 3
        text = agg.prometheus()
        assert 'paddle_tpu_serve_kv_frag_frac 0.25' in text

    def test_memory_pressure_is_live_alert(self):
        from paddle_tpu.telemetry.live import LiveAggregator
        agg = LiveAggregator()
        agg.write({'kind': 'memory_pressure', 'observed_bytes': 9,
                   'budget_bytes': 10})
        alerts = agg.snapshot()['alerts']
        assert alerts and alerts[-1]['kind'] == 'memory_pressure'

    def test_cluster_frame_carries_memory_columns(self):
        from paddle_tpu.telemetry.cluster import ClusterPublisher
        from paddle_tpu.telemetry import set_gauge
        set_gauge('memory.device_bytes', 12345)
        set_gauge('memory.host_rss', 67890)
        pub = ClusterPublisher(rank=0, interval_s=3600)
        frame = pub.frame()
        assert frame['mem_device_bytes'] == 12345
        assert frame['mem_host_rss'] == 67890

    def test_trainer_compiled_text_notes_memory(self):
        """The FREE extraction path: ParallelTrainer.compiled_text()
        already holds a Compiled — one memory_compiled row appears
        with no arming and no extra compile."""
        import numpy as np
        from jax.sharding import Mesh
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.parallel import ParallelTrainer
        paddle.seed(0)
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ('dp',))
        tr = ParallelTrainer(net, opt, loss_fn=nn.MSELoss(),
                             mesh=mesh)
        x = jnp.ones((4, 4), jnp.float32)
        y = jnp.zeros((4, 2), jnp.float32)
        tr.step(x, y)
        tr.compiled_text()
        evs = telemetry.events('memory_compiled')
        assert evs and evs[-1]['name'] == 'ParallelTrainer.step'
        assert evs[-1]['source'] == 'trainer-hlo'
        assert evs[-1]['compiled_peak_bytes'] > 0


# --------------------------------- calibration closes the loop ------
class TestCalibrationBias:
    """memory_compiled events -> calibrate_costmodel 'peak_memory'
    bias -> planner HBM gate: the memory analogue of the PR-8
    collective alpha/beta loop."""

    def _load_tool(self, name):
        import importlib.util
        path = os.path.join(_REPO, 'tools', f'{name}.py')
        spec = importlib.util.spec_from_file_location(name, path)
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        return tool

    def test_fitter_emits_peak_memory_bias(self, tmp_path):
        cc = self._load_tool('calibrate_costmodel')
        rows = [(900, 1000), (1800, 2000), (4500, 5000)]
        with open(tmp_path / 'telemetry-r0.jsonl', 'w') as f:
            for i, (p, c) in enumerate(rows):
                f.write(json.dumps(
                    {'kind': 'memory_compiled', 'ts': float(i),
                     'name': f'm{i}', 'predicted_peak_bytes': p,
                     'compiled_peak_bytes': c}) + '\n')
        out = str(tmp_path / 'cal.json')
        assert cc.main([str(tmp_path), '-o', out]) == 0
        from paddle_tpu.analysis import costmodel
        cal = costmodel.load_calibration(out)
        row = cal.per_op['peak_memory']
        # compiled/predicted is exactly 10/9 in every sample
        assert row['bias'] == pytest.approx(10 / 9, rel=1e-4)
        assert row['samples'] == 3

    def test_fitter_harvests_run_report_memory_section(self, tmp_path):
        cc = self._load_tool('calibrate_costmodel')
        doc = {'schema_version': 1, 'collectives_cmp': {},
               'memory': {'modules': {
                   'Model.train_batch': {
                       'predicted_peak_bytes': 500,
                       'compiled_peak_bytes': 1000}}}}
        with open(tmp_path / 'report.json', 'w') as f:
            json.dump(doc, f)
        out = str(tmp_path / 'cal.json')
        assert cc.main([str(tmp_path / 'report.json'),
                        '-o', out]) == 0
        table = json.load(open(out))
        assert table['per_op']['peak_memory']['bias'] == \
            pytest.approx(2.0)

    def test_fit_peak_memory_skips_junk(self):
        cc = self._load_tool('calibrate_costmodel')
        assert cc.fit_peak_memory([]) is None
        assert cc.fit_peak_memory([(0, 100), (100, 0)]) is None
        row = cc.fit_peak_memory([(100, 150), (0, 5)])
        assert row['samples'] == 1
        assert row['bias'] == pytest.approx(1.5)

    def test_planner_hbm_gate_applies_bias(self):
        """A biased calibration scales every candidate's peak_bytes —
        the gate judges at measured accuracy, not nominal."""
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.analysis import planner, costmodel
        paddle.seed(0)

        def mlp():
            paddle.seed(0)
            return nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                                 nn.Linear(32, 4))

        batch = (jax.ShapeDtypeStruct((16, 16), jnp.float32),)
        base = planner.plan_model(mlp(), batch, chips=8,
                                  include_pp=False, name='m')
        cal = costmodel.Calibration(
            per_op={'peak_memory': {'bias': 2.0, 'samples': 3}})
        scaled = planner.plan_model(mlp(), batch, chips=8,
                                    include_pp=False, name='m',
                                    calibration=cal)
        by_key = {(tuple(sorted(p.mesh_axes.items())), p.assignment):
                  p.peak_bytes for p in base.candidates}
        assert scaled.candidates
        for p in scaled.candidates:
            k = (tuple(sorted(p.mesh_axes.items())), p.assignment)
            assert p.peak_bytes == int(by_key[k] * 2.0)
