"""Cluster observability plane (telemetry.cluster + friends).

Contracts pinned here:

- the non-blocking stats-frame channel on ``HostCollectives``
  (``post_stats``/``read_stats``/``read_all_stats``): overwrite
  semantics, corrupt-frame tolerance, heartbeat join;
- ``ClusterPublisher``: folds the boundary-rate stream (steps flushes,
  compiles, retraces, collective_observed, checkpoint commits) into
  rolling windows and publishes frames at its interval — and a
  publisher-enabled trainer loop stays SYNC-FREE under a device→host
  transfer guard;
- ``ClusterAggregator``: joins frames + heartbeats into the cluster
  view — per-rank skew, straggler ATTRIBUTION (compute skew beats
  step skew beats behind beats stale), critical-path breakdown, loss
  divergence — and a missing/stale/corrupt rank DEGRADES the view
  (stale-marked) instead of crashing it;
- monitor latches: ``straggler_suspect`` fires once per attribution
  edge (re-arming on clear / new rank), ``rank_divergence`` fires
  once per divergence edge with hysteresis;
- the ``MetricsServer`` source registry: one port serves the primary
  aggregator AND named sources (``/cluster/status.json``,
  ``/cluster/metrics``, concatenated ``/metrics``), ``attach_source``
  reuses a running server instead of double-binding;
- watchdog budgets from MEASURED step profiles: ``Budget.
  note_measured`` refreshes default/cost-model budgets, never an
  operator's explicit deadline;
- ``run_report``: the cluster section (per-rank skew + straggler +
  live suspects), and ``--follow`` live-tail mode;
- the EVENT_KINDS coverage meta-test extension lives in
  tests/test_event_live.py (every declared kind rendered by
  run_report or explicitly ignore-listed).

NOTE this file must sort alphabetically before test_host_embedding.py:
the seed's tier-1 run aborts there (XLA compiler crash) and later
files never execute.
"""
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.distributed.collective import (FileKVStore,
                                               HostCollectives)
from paddle_tpu.resilience.watchdog import Budget, resolve_watchdog
from paddle_tpu.telemetry import (ClusterAggregator, ClusterPublisher,
                                  DriftMonitor, LiveAggregator,
                                  MetricsServer, SLOMonitor,
                                  attach_source)
from paddle_tpu.telemetry.cluster import (attribute_straggler,
                                          critical_path,
                                          loss_divergence,
                                          resolve_cluster_stats)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_recorder():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _pair(tmp_path, world=2):
    kv = FileKVStore(str(tmp_path / 'kv'))
    return [HostCollectives(client=kv, rank=r, world=world)
            for r in range(world)]


def _steps_event(step_lo, n=4, ms=100.0, loss=1.0, tag='soak',
                 **cols):
    ev = {'kind': 'steps', 'tag': tag, 'n': n, 'step_lo': step_lo,
          'step_hi': step_lo + n - 1,
          'step': list(range(step_lo, step_lo + n)),
          'step_time_ms': [ms] * n, 'loss': [loss] * n}
    for k, v in cols.items():
        ev[k] = [v] * n
    return ev


def _emit_steps(step_lo, **kw):
    ev = _steps_event(step_lo, **kw)
    return telemetry.event(ev.pop('kind'), **ev)


# ------------------------------------------------ stats-frame channel --
class TestStatsChannel:
    def test_post_read_roundtrip_and_overwrite(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        assert hc0.post_stats({'v': 1, 'seq': 1})
        assert hc1.read_stats(0) == {'v': 1, 'seq': 1}
        assert hc0.post_stats({'v': 1, 'seq': 2})     # overwrite
        assert hc1.read_stats(0)['seq'] == 2
        assert hc1.read_stats(1) is None              # never posted
        hc1.post_stats({'v': 1, 'seq': 9})
        allf = hc0.read_all_stats()
        assert set(allf) == {0, 1}

    def test_corrupt_frame_reads_as_none(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        hc0.client.key_value_set_bytes('ptpu/cstats/r0',
                                       b'{not json')
        assert hc1.read_stats(0) is None
        # and a non-dict JSON is also rejected
        hc0.client.key_value_set_bytes('ptpu/cstats/r0', b'[1,2]')
        assert hc1.read_stats(0) is None

    def test_no_client_is_inert(self):
        hc = HostCollectives(client=None, rank=0, world=1)
        assert hc.post_stats({'v': 1}) is False
        assert hc.read_all_stats() == {}

    def test_heartbeat_join(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        hc0.client.key_value_set_bytes(
            'ptpu/hb/r1',
            json.dumps({'ts': time.time() - 2.5}).encode())
        ages = hc0.read_heartbeats()
        assert 1 in ages and 2.0 < ages[1] < 10.0


# ------------------------------------------------------- publisher --
class TestClusterPublisher:
    def test_frame_contents(self, tmp_path):
        (hc0,) = _pair(tmp_path, world=1)
        pub = ClusterPublisher(transport=hc0, interval_s=1e9)
        pub.write(_steps_event(0, ms=50.0, loss=2.0,
                               compute_ms=40.0, coll_ms=8.0))
        pub.write({'kind': 'compile', 'dur_s': 1.5})
        pub.write({'kind': 'retrace'})
        pub.write({'kind': 'collective_observed', 'us': 30.0,
                   'predicted_us': 10.0})
        pub.write({'kind': 'checkpoint_commit', 'step': 3})
        f = pub.frame()
        assert f['v'] == 1 and f['rank'] == 0
        assert f['step'] == 3 and f['steps_total'] == 4
        assert f['last_commit_step'] == 3
        assert f['step_ms']['p50'] == 50.0
        assert f['compiles'] == 1 and f['retraces'] == 1
        assert f['compile_s'] == 1.5
        assert f['coll_ratio'] == 3.0
        assert f['cols']['compute_ms'] == 40.0
        assert f['cols']['coll_ms'] == 8.0
        assert f['loss']['mean'] == 2.0

    def test_publish_interval_and_subscription(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        pub = ClusterPublisher(transport=hc0, interval_s=0.0).install()
        _emit_steps(0)
        assert pub.published >= 1
        assert hc1.read_stats(0)['steps_total'] == 4
        pub.uninstall()
        before = pub.published
        _emit_steps(4)
        assert pub.published == before    # stream detached
        # huge interval: frames aggregate but do not post
        pub2 = ClusterPublisher(transport=hc0,
                                interval_s=1e9).install()
        _emit_steps(8)
        assert pub2.published == 0
        assert pub2.steps_total == 4
        pub2.uninstall()

    def test_publisher_never_raises(self, tmp_path):
        (hc0,) = _pair(tmp_path, world=1)
        pub = ClusterPublisher(transport=hc0, interval_s=0.0)
        pub.write({'kind': 'steps', 'step_time_ms': 'garbage'})
        pub.write({'not even': 'an event'})
        pub.write({'kind': 'compile'})    # still alive

    def test_resolve_cluster_stats_posture(self, monkeypatch):
        assert resolve_cluster_stats(False) is None
        assert resolve_cluster_stats(True) == 2.0
        assert resolve_cluster_stats(0.5) == 0.5
        monkeypatch.delenv('PADDLE_TPU_CLUSTER_STATS', raising=False)
        assert resolve_cluster_stats() is None
        monkeypatch.setenv('PADDLE_TPU_CLUSTER_STATS', '0')
        assert resolve_cluster_stats() is None
        monkeypatch.setenv('PADDLE_TPU_CLUSTER_STATS', '1')
        assert resolve_cluster_stats() == 2.0
        monkeypatch.setenv('PADDLE_TPU_CLUSTER_STATS', '0.25')
        assert resolve_cluster_stats() == 0.25
        # explicit False beats an armed env
        assert resolve_cluster_stats(False) is None


# ---------------------------------------------- attribution helpers --
class TestAttribution:
    def test_compute_skew_wins(self):
        pr = {0: {'compute_ms': 2.0, 'step_p50_ms': 400.0, 'step': 10},
              1: {'compute_ms': 390.0, 'step_p50_ms': 400.0,
                  'step': 10}}
        s = attribute_straggler(pr)
        assert s['rank'] == 1 and s['cause'] == 'compute_skew'
        assert s['skew'] > 1.75 and s['behind'] == 0

    def test_step_skew_fallback(self):
        pr = {0: {'step_p50_ms': 100.0, 'step': 10},
              1: {'step_p50_ms': 350.0, 'step': 10}}
        s = attribute_straggler(pr)
        assert s['rank'] == 1 and s['cause'] == 'step_skew'

    def test_behind_and_stale(self):
        pr = {0: {'step_p50_ms': 100.0, 'step': 40},
              1: {'stale': True, 'step': 20, 'hb_age_s': 9.0}}
        s = attribute_straggler(pr, hb_stale_s=5.0)
        assert s['rank'] == 1 and s['cause'] == 'behind'
        assert s['behind'] == 20 and s['hb_stale'] is True
        # stale with no step info at all
        pr2 = {0: {'step_p50_ms': 100.0, 'step': 40},
               1: {'stale': True}}
        s2 = attribute_straggler(pr2)
        assert s2['rank'] == 1 and s2['cause'] == 'stale'

    def test_healthy_cluster_attributes_nothing(self):
        pr = {0: {'step_p50_ms': 100.0, 'step': 40,
                  'compute_ms': 90.0},
              1: {'step_p50_ms': 104.0, 'step': 40,
                  'compute_ms': 93.0}}
        assert attribute_straggler(pr) is None

    def test_critical_path(self):
        pr = {0: {'step_p50_ms': 400.0, 'compute_ms': 2.0,
                  'coll_ms': 395.0, 'wait_ms_mean': 1.0},
              1: {'step_p50_ms': 402.0, 'compute_ms': 390.0,
                  'coll_ms': 5.0}}
        cp = critical_path(pr)
        assert cp['step_ms'] == 402.0
        assert cp['compute_ms'] == 390.0
        assert cp['collective_ms'] == 5.0
        assert cp['straggler_wait_ms'] == 390.0
        assert cp['host_wait_ms'] == 1.0
        assert critical_path({}) == {}

    def test_loss_divergence(self):
        pr = {0: {'loss_mean': 1.0}, 1: {'loss_mean': 1.0}}
        d = loss_divergence(pr)
        assert d['spread'] == 0.0 and not d['divergent']
        pr[1]['loss_mean'] = 2.0
        d = loss_divergence(pr, band=0.25)
        assert d['divergent'] and d['spread'] > 0.25
        assert loss_divergence({0: {'loss_mean': 1.0}}) is None


# ------------------------------------------------------ aggregator --
class TestClusterAggregator:
    def _publish(self, hc, rank, ms, compute, coll, step=10,
                 loss=1.0, ts=None):
        pub = ClusterPublisher(transport=hc, interval_s=0.0)
        pub.write(_steps_event(step - 3, ms=ms, loss=loss,
                               compute_ms=compute, coll_ms=coll))
        frame = pub.frame()
        if ts is not None:
            frame['ts'] = ts
        hc.post_stats(frame)
        return frame

    def test_view_attributes_straggler(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        self._publish(hc0, 0, ms=400.0, compute=2.0, coll=395.0)
        self._publish(hc1, 1, ms=400.0, compute=390.0, coll=5.0)
        agg = ClusterAggregator(transport=hc0, stale_after_s=30.0)
        view = agg.snapshot()
        assert view['world'] == 2 and not view['degraded']
        assert view['straggler']['rank'] == 1
        assert view['straggler']['cause'] == 'compute_skew'
        assert view['straggler']['skew'] > 1.75
        assert view['critical_path']['compute_ms'] == 390.0
        assert view['critical_path']['straggler_wait_ms'] == 390.0
        assert view['ranks']['0']['step'] == 10
        prom = agg.prometheus()
        assert 'paddle_tpu_cluster_straggler_rank 1' in prom
        assert 'paddle_tpu_cluster_rank_step{rank="0"} 10' in prom

    def test_missing_and_stale_degrade_not_crash(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        self._publish(hc0, 0, ms=100.0, compute=90.0, coll=5.0)
        agg = ClusterAggregator(transport=hc0, stale_after_s=5.0,
                                min_collect_gap_s=0.0)
        view = agg.snapshot()
        assert view['degraded'] and view['missing'] == [1]
        assert view['ranks']['1']['stale']
        # now rank 1 published long ago -> stale-marked, last
        # evidence retained
        self._publish(hc1, 1, ms=100.0, compute=90.0, coll=5.0,
                      step=6, ts=time.time() - 60.0)
        view = agg.snapshot()
        assert view['stale'] == [1]
        assert view['ranks']['1']['stale']
        assert view['ranks']['1']['step'] == 6
        assert view['straggler']['rank'] == 1    # behind + quiet
        # corrupt frame: also degraded, never a crash
        hc1.client.key_value_set_bytes('ptpu/cstats/r1', b'xx')
        view = agg.snapshot()
        assert 1 in view['missing']

    def test_staleness_is_clock_offset_immune(self, tmp_path):
        """Staleness is judged by seq advancement on the OBSERVER's
        monotonic clock: a healthy rank on a host whose wall clock is
        offset by minutes must NOT be stale-marked (offsets under the
        clock tolerance never matter; beyond it, only a frame whose
        seq also stops advancing goes stale via the wall fallback
        bound for the aggregator-restart cold start)."""
        hc0, hc1 = _pair(tmp_path)
        agg = ClusterAggregator(transport=hc0, stale_after_s=0.2,
                                min_collect_gap_s=0.0,
                                clock_tolerance_s=120.0)
        # rank 1's host clock runs 60s BEHIND — frame looks ancient
        # by wall delta, but its seq keeps advancing
        pub1 = ClusterPublisher(transport=hc1, interval_s=0.0)
        for i in range(3):
            pub1.write(_steps_event(i * 4, ms=100.0))
            frame = pub1.frame()
            frame['ts'] = time.time() - 60.0
            hc1.post_stats(frame)
            view = agg.collect()
            assert not view['ranks']['1']['stale'], (i, view)
        # seq stops advancing -> stale after stale_after_s of
        # observation, clock offset or not
        time.sleep(0.25)
        view = agg.collect()
        assert view['ranks']['1']['stale']
        # cold start next to a LONG-dead frame: the wall fallback
        # bound catches it on first sight
        agg2 = ClusterAggregator(transport=hc0, stale_after_s=0.2,
                                 min_collect_gap_s=0.0,
                                 clock_tolerance_s=5.0)
        assert agg2.collect()['ranks']['1']['stale']

    def test_monitor_latches(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        agg = ClusterAggregator(transport=hc0, stale_after_s=30.0,
                                min_collect_gap_s=0.0)
        slo = agg.attach_monitor(SLOMonitor())
        drift = agg.attach_monitor(DriftMonitor())
        self._publish(hc0, 0, ms=400.0, compute=2.0, coll=395.0,
                      loss=1.0)
        self._publish(hc1, 1, ms=400.0, compute=390.0, coll=5.0,
                      loss=2.0)
        agg.snapshot()
        agg.snapshot()
        agg.snapshot()
        suspects = telemetry.events('straggler_suspect')
        assert len(suspects) == 1            # latched: one edge
        assert suspects[0]['suspect'] == 1
        assert suspects[0]['cause'] == 'compute_skew'
        divs = telemetry.events('rank_divergence')
        assert len(divs) == 1
        assert divs[0]['spread'] > 0.25
        assert len(slo.breaches) == 1 and len(drift.detections) == 1
        # straggler clears -> re-arm -> new edge fires again
        self._publish(hc1, 1, ms=400.0, compute=3.0, coll=395.0,
                      loss=1.0)
        self._publish(hc0, 0, ms=400.0, compute=2.0, coll=396.0,
                      loss=1.0)
        agg.snapshot()
        self._publish(hc0, 0, ms=400.0, compute=390.0, coll=5.0,
                      loss=1.0)
        agg.snapshot()
        suspects = telemetry.events('straggler_suspect')
        assert len(suspects) == 2
        assert suspects[1]['suspect'] == 0

    def test_alerts_land_in_live_aggregator_ring(self, tmp_path):
        live = LiveAggregator().install()
        try:
            telemetry.event('straggler_suspect', suspect=1,
                            cause='compute_skew', skew=2.0)
            telemetry.event('rank_divergence', spread=0.5, band=0.25)
            kinds = [a.get('kind') for a in live.alerts]
            assert kinds == ['straggler_suspect', 'rank_divergence']
        finally:
            live.uninstall()


# ------------------------------------------------- source registry --
class TestMetricsSourceRegistry:
    def test_one_port_serves_both_views(self, tmp_path):
        hc0, hc1 = _pair(tmp_path)
        ClusterPublisher(transport=hc0, interval_s=0.0).publish()
        ClusterPublisher(transport=hc1, interval_s=0.0).publish()
        cagg = ClusterAggregator(transport=hc0, stale_after_s=30.0,
                                 min_collect_gap_s=0.0)
        live = LiveAggregator()
        srv = MetricsServer(live, port=0).start()
        try:
            srv.add_source('cluster', cagg)
            base = srv.url
            doc = json.loads(urllib.request.urlopen(
                base + '/cluster/status.json', timeout=10).read())
            assert doc['world'] == 2
            cm = urllib.request.urlopen(
                base + '/cluster/metrics', timeout=10).read().decode()
            assert 'paddle_tpu_cluster_world_size 2' in cm
            # the concatenated /metrics carries BOTH planes
            m = urllib.request.urlopen(
                base + '/metrics', timeout=10).read().decode()
            assert 'paddle_tpu_uptime_seconds' in m
            assert 'paddle_tpu_cluster_world_size' in m
            # health names the sources; primary routes still work
            h = json.loads(urllib.request.urlopen(
                base + '/healthz', timeout=10).read())
            assert h['sources'] == ['cluster']
            routes = json.loads(urllib.request.urlopen(
                base + '/', timeout=10).read())['routes']
            assert '/cluster/status.json' in routes
            # unknown source 404s
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + '/nope/status.json',
                                       timeout=10)
        finally:
            srv.stop()

    def test_registry_only_server(self, tmp_path):
        (hc0,) = _pair(tmp_path, world=1)
        ClusterPublisher(transport=hc0, interval_s=0.0).publish()
        cagg = ClusterAggregator(transport=hc0, stale_after_s=30.0)
        srv = MetricsServer(None, port=0).start()
        try:
            srv.add_source('cluster', cagg)
            base = srv.url
            doc = json.loads(urllib.request.urlopen(
                base + '/cluster/status.json', timeout=10).read())
            assert doc['world'] == 1
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + '/status.json',
                                       timeout=10)
            m = urllib.request.urlopen(
                base + '/metrics', timeout=10).read().decode()
            assert 'paddle_tpu_cluster_world_size' in m
        finally:
            srv.stop()

    def test_attach_source_reuses_running_server(self, tmp_path):
        (hc0,) = _pair(tmp_path, world=1)
        cagg = ClusterAggregator(transport=hc0, stale_after_s=30.0)
        live = LiveAggregator()
        srv = MetricsServer(live, port=0).start()
        try:
            got, created = attach_source('cluster', cagg)
            assert got is srv and created is False
            assert 'cluster' in srv.sources
        finally:
            srv.stop()
        # no running server + no port -> no HTTP
        got, created = attach_source('cluster', cagg, port=None)
        assert got is None and created is False
        # no running server + port -> fresh registry-only server
        got, created = attach_source('cluster', cagg, port=0)
        try:
            assert created is True and got.port
        finally:
            got.stop()

    def test_bad_source_names_rejected(self):
        srv = MetricsServer(None)
        with pytest.raises(ValueError):
            srv.add_source('metrics', object())
        with pytest.raises(ValueError):
            srv.add_source('a/b', object())
        with pytest.raises(TypeError):
            srv.add_source('ok', object())   # no snapshot/prometheus


# ------------------------------------------------ measured budgets --
class TestMeasuredBudgets:
    def test_default_budget_adapts(self):
        b = Budget()
        assert b.step_source == 'default'
        new = b.note_measured([0.010] * 32)
        assert new == b.step_s and b.step_source == 'measured'
        # 10ms p95 x slack 8 -> clamped to the 1s floor
        assert b.step_s == 1.0
        new = b.note_measured([0.5] * 32)
        assert b.step_s == pytest.approx(4.0)

    def test_costmodel_budget_yields_to_measured(self):
        b = Budget.from_costmodel(500_000)   # 0.5s est -> 5s? (x8)
        assert b.step_source == 'costmodel'
        est = b.step_s
        assert b.note_measured([2.0] * 32) is not None
        assert b.step_s != est and b.step_source == 'measured'

    def test_explicit_budget_is_a_contract(self):
        b = Budget(step_s=30)
        assert b.step_source == 'explicit'
        assert b.note_measured([0.01] * 64) is None
        assert b.step_s == 30.0
        # env-armed explicit numbers are explicit too
        b2 = Budget.from_env('step=12,grace=1')
        assert b2.step_source == 'explicit'
        assert b2.note_measured([0.01] * 64) is None
        # env '1' = defaults = adaptable
        b3 = Budget.from_env('1')
        assert b3.step_source == 'default'
        assert b3.note_measured([0.01] * 64) is not None

    def test_too_few_samples_no_change(self):
        b = Budget()
        assert b.note_measured([0.01] * 3) is None
        assert b.step_source == 'default'

    def test_resolve_watchdog_preserves_source(self):
        assert resolve_watchdog({'step_s': 9}).step_source == \
            'explicit'
        assert resolve_watchdog(True).step_source == 'default'

    def test_trainer_feeds_measured_budget(self):
        """The engine-side plumbing: _note_measured_step refreshes an
        armed non-explicit budget every 32 steady-state steps."""
        from paddle_tpu.parallel.engine import ParallelTrainer
        trainer = ParallelTrainer.__new__(ParallelTrainer)
        from collections import deque
        trainer._measured_dts = deque(maxlen=256)
        trainer._measured_n = 0

        class _WD:
            budget = Budget()
        trainer._watchdog = _WD()
        for _ in range(32):
            trainer._note_measured_step(0.25, telemetry)
        assert _WD.budget.step_source == 'measured'
        assert _WD.budget.step_s == pytest.approx(2.0)
        assert telemetry.get_recorder().gauges[
            'watchdog.measured_step_s'] == pytest.approx(2.0)


# ------------------------------------------------ sync-free publisher --
class TestPublisherStaysSyncFree:
    @staticmethod
    def _loop(tmp_path, with_publisher):
        """Eight hapi steps after the compile, under a device->host
        transfer guard: (losses, compile events, publisher, its
        transport)."""
        pub = hc0 = None
        telemetry.reset()
        telemetry.enable(None, flush_interval=4)
        if with_publisher:
            (hc0,) = _pair(tmp_path, world=1)
            pub = ClusterPublisher(transport=hc0,
                                   interval_s=0.0).install()
        try:
            paddle.seed(0)
            model = paddle.hapi.Model(nn.Sequential(
                nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4)))
            opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=model.parameters())
            model.prepare(optimizer=opt, loss=nn.MSELoss())
            model._check_finite_steps = False
            rs = np.random.RandomState(0)
            x = rs.randn(8, 16).astype('float32')
            y = rs.randn(8, 4).astype('float32')
            model.train_batch(x, y)          # compile outside guard
            acc = telemetry.step_accumulator('cobs')
            losses = []
            with jax.transfer_guard_device_to_host('disallow'):
                for i in range(8):
                    loss, _ = model.train_batch(x, y)
                    acc.observe(step=i, step_time_s=0.01, loss=loss)
                    losses.append(loss)
            acc.flush()
            return ([float(np.asarray(v)) for v in losses],
                    len(telemetry.events('compile')), pub, hc0)
        finally:
            if pub is not None:
                pub.uninstall()
            telemetry.disable()

    def test_trainer_loop_with_publisher_sync_free(self, tmp_path):
        """A hapi loop with a ClusterPublisher installed (real KV
        writes included) must not read any device value: the
        publisher consumes only the flushed boundary-rate stream."""
        _losses, _compiles, pub, hc0 = self._loop(tmp_path, True)
        assert pub.published >= 1
        assert hc0.read_stats(0)['steps_total'] >= 4

    def test_publisher_changes_no_loss_and_no_compile(self, tmp_path):
        on = self._loop(tmp_path, True)
        off = self._loop(tmp_path, False)
        assert on[0] == off[0]
        assert on[1] == off[1]


# --------------------------------------------------- run_report side --
class TestRunReportCluster:
    def _write_stream(self, d, rank, ms, n_flushes=3, suspect=None):
        with open(os.path.join(d, f'telemetry-r{rank}.jsonl'),
                  'w') as f:
            for i in range(n_flushes):
                f.write(json.dumps(dict(
                    _steps_event(i * 4, ms=ms),
                    ts=100.0 + i, t=float(i), rank=rank)) + '\n')
            if suspect is not None:
                f.write(json.dumps(
                    {'kind': 'straggler_suspect', 'ts': 104.0,
                     't': 4.0, 'rank': rank, 'suspect': suspect,
                     'cause': 'compute_skew', 'skew': 2.5}) + '\n')

    def test_cluster_section_and_timeline(self, tmp_path):
        d = str(tmp_path)
        self._write_stream(d, 0, ms=100.0, suspect=1)
        self._write_stream(d, 1, ms=400.0)
        out = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'), d,
             '--json'],
            capture_output=True, text=True)
        rep = json.loads(out.stdout)
        cl = rep['cluster']
        assert set(cl['ranks']) == {'0', '1'}
        assert cl['ranks']['1']['skew'] == pytest.approx(1.6)
        assert cl['straggler']['rank'] == 1
        assert cl['suspects'][0]['suspect'] == 1
        kinds = [r['kind'] for r in rep['timeline']]
        assert 'straggler_suspect' in kinds
        # single-rank runs have no cluster section
        d1 = str(tmp_path / 'single')
        os.makedirs(d1)
        self._write_stream(d1, 0, ms=100.0)
        out = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'), d1,
             '--json'],
            capture_output=True, text=True)
        assert json.loads(out.stdout)['cluster'] is None

    def test_follow_live_tail(self, tmp_path):
        d = str(tmp_path)
        self._write_stream(d, 0, ms=100.0)
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'), d,
             '--follow', '--interval', '0.2', '--refreshes', '3'],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        time.sleep(0.3)
        # a SECOND rank appears while --follow runs: the next render
        # must pick it up (live tail, not a one-shot)
        self._write_stream(d, 1, ms=400.0)
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 0
        assert out.count('--follow') == 3
        assert out.count('paddle_tpu run report') == 3
        assert 'cluster (per-rank step skew)' in out

    def test_follow_waits_for_empty_dir(self, tmp_path):
        out = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'),
             str(tmp_path), '--follow', '--interval', '0.05',
             '--refreshes', '2'],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0
        assert 'waiting for telemetry' in out.stdout


# ------------------------------------------------------ chaos e2e --
# slow: spins real worker interpreters; nothing automatic runs it.
@pytest.mark.slow
class TestClusterObsE2E:
    def test_throttled_rank_attributed_live_and_kill_degrades(self):
        """2-proc ChaosCluster, rank 1 throttled and then SIGKILLed:
        a mid-run scrape of /cluster/status.json must attribute rank 1
        with populated skew, the kill must DEGRADE the view (rank 1
        stale-marked or missing while the server still answers), and
        the soak must stay green (the plane costs nothing)."""
        import threading
        from paddle_tpu.resilience.chaos import (ChaosCluster,
                                                 FaultPlan)
        plan = FaultPlan(seed=7, faults=(
            [{'kind': 'slow_rank', 'at_step': s, 'rank': 1,
              'delay_s': 0.35} for s in range(3, 10)]
            + [{'kind': 'sigkill', 'at_step': 14, 'rank': 1}]))
        cluster = ChaosCluster(
            procs=2, plan=plan, steps=20, save_every=2,
            collective_timeout_s=20.0, watchdog='step=60,grace=2',
            deadline_s=180.0, cluster_stats=True,
            # hold the killed rank down for some 4 s: the stale
            # threshold is 1.5 s, so the 200 ms scraper sees the
            # degraded view before the respawn publishes again
            restart_backoff=4.0, restart_backoff_max=5.0,
            extra_env={'PADDLE_TPU_SOAK_FLUSH': '2',
                       'PADDLE_TPU_SOAK_STALE_AFTER': '1.5'})
        result = {}

        def _run():
            result['report'] = cluster.run()

        th = threading.Thread(target=_run, daemon=True)
        th.start()
        snaps = []
        t0 = time.time()
        while th.is_alive() and time.time() - t0 < 170:
            try:
                with open(cluster.cluster_port_file) as f:
                    port = json.load(f)['port']
                snaps.append(json.loads(urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/cluster/status.json',
                    timeout=2).read()))
            except Exception:
                pass
            time.sleep(0.2)
        th.join(timeout=30)
        rep = result['report']
        assert rep['rc'] == 0 and rep['ok'], rep['violations']
        assert any(e.get('fault') == 'sigkill'
                   for e in rep['injected'])
        blamed = [s['straggler']['rank'] for s in snaps
                  if (s.get('straggler') or {}).get('rank') is not None]
        hits = [s for s in snaps
                if (s.get('straggler') or {}).get('rank') == 1]
        assert hits, f'no scrape attributed rank 1 ({len(snaps)})'
        assert hits[0]['straggler']['skew'] > 1.0
        assert hits[0]['critical_path']
        # a waiter may be blamed for a window; rank 1 must dominate
        assert len(blamed) - len(hits) <= len(hits), blamed
        assert [s for s in snaps if s.get('degraded')
                and ((s.get('ranks') or {}).get('1', {}).get('stale')
                     or 1 in (s.get('missing') or []))], \
            'the kill never showed as a degraded view'
