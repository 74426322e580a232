"""Live observability plane (telemetry.live / monitors / httpd).

Contracts pinned here:

- ``Recorder.subscribe`` delivers exactly the boundary-rate stream
  (never signal-path records), swallows consumer exceptions, and
  unsubscribes cleanly;
- the ``LiveAggregator`` rolling windows (TTFT/TPOT/step-time
  percentiles, token rates, eviction-by-cause counters, occupancy
  gauges) populate from ``serve_step``/``serve_request``/``steps``
  events and render as both ``/status.json`` and Prometheus text;
- the HTTP status server answers ``/healthz`` ``/status.json``
  ``/metrics`` ``/requests/<rid>`` and 404s unknowns;
- scraping ``/metrics`` DURING a live serving run changes no
  numerics: token streams bit-exact vs a server-off engine on the
  same requests, zero extra compiles (ISSUE-13 acceptance);
- SLO/drift monitors fire ``slo_breach``/``drift_detected`` as
  LATCHED edges — a seeded drift injection (one collective's observed
  us inflated) fires EXACTLY one event, visible in ``/status.json``
  and in ``run_report`` (--json serving section + timeline);
- a NON-serving trainer loop with the aggregator installed stays
  sync-free under a device→host transfer guard;
- the recorder meta-test: every event kind emitted anywhere under
  ``paddle_tpu/`` is declared in ``EVENT_KINDS`` (with the new
  ``serve_trace``/``slo_breach``/``drift_detected`` kinds), and
  ``serve_request`` events carry their full field schema.

NOTE this file must sort alphabetically before test_host_embedding.py:
the seed's tier-1 run aborts there (XLA compiler crash) and later
files never execute.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.resilience.watchdog import Budget
from paddle_tpu.serving import (ServeConfig, ServingEngine,
                                poisson_requests)
from paddle_tpu.telemetry import (DriftMonitor, LiveAggregator,
                                  MetricsServer, RateCounter,
                                  RollingWindow, SLOMonitor,
                                  resolve_metrics_port)
from paddle_tpu.telemetry.recorder import EVENT_KINDS

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Each test gets a virgin process-global recorder."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _get(url):
    return urllib.request.urlopen(url, timeout=10).read().decode()


def _tiny_model(**kw):
    kw.setdefault('num_layers', 2)
    kw.setdefault('hidden_size', 32)
    kw.setdefault('num_heads', 2)
    kw.setdefault('max_seq_len', 64)
    paddle.seed(7)
    m = gpt_tiny(**kw)
    m.eval()
    return m


def _tiny_config(**kw):
    kw.setdefault('block_size', 4)
    kw.setdefault('max_slots', 4)
    kw.setdefault('decode_span', 2)
    kw.setdefault('prompt_buckets', (4, 8))
    kw.setdefault('batch_buckets', (1, 2, 4))
    kw.setdefault('prefill_batch', 2)
    kw.setdefault('max_model_len', 32)
    kw.setdefault('temperature', 0.0)
    return ServeConfig(**kw)


def _tiny_load(model, n=5, seed=1):
    return poisson_requests(
        n, rate_rps=500.0, prompt_lens=(3, 5), new_tokens=(4, 6),
        vocab_size=model.config.vocab_size, seed=seed)


# ------------------------------------------------ rolling primitives --
class TestRollingPrimitives:
    def test_window_percentiles_and_eviction(self):
        win = RollingWindow(window_s=10.0)
        for i, v in enumerate([1.0, 2.0, 3.0, 4.0]):
            win.add(v, now=100.0 + i)
        pct = win.percentiles(now=104.0)
        assert pct['count'] == 4 and pct['max'] == 4.0
        assert pct['p50'] == 3.0
        # later only the newest sample is still inside the window
        pct = win.percentiles(now=112.5)
        assert pct['count'] == 1 and pct['p50'] == 4.0
        assert win.percentiles(now=200.0) == {}

    def test_window_ignores_none(self):
        win = RollingWindow()
        win.add(None)
        assert win.percentiles() == {}

    def test_rate_counter_total_rate_windowed(self):
        rc = RateCounter(window_s=10.0)
        rc._t0 = 100.0
        for i in range(5):
            rc.add(2, now=100.0 + i)
        assert rc.total == 10
        assert rc.windowed(now=104.0) == 10
        # 10 increments over min(window, age)=4s
        assert rc.rate(now=104.0) == pytest.approx(10 / 4.0)
        # old increments age out of rate and windowed sums
        assert rc.windowed(now=112.5) == 4
        assert rc.total == 10


# ------------------------------------------------ recorder.subscribe --
class TestRecorderSubscribe:
    def test_subscriber_receives_stream(self):
        rec = telemetry.get_recorder()
        seen = []
        rec.subscribe(seen.append)
        telemetry.event('serve_step', decoded=3)
        telemetry.event('compile', name='x', dur_s=0.1)
        assert [e['kind'] for e in seen] == ['serve_step', 'compile']

    def test_unsubscribe_stops_delivery(self):
        rec = telemetry.get_recorder()
        seen = []
        rec.subscribe(seen.append)
        rec.unsubscribe(seen.append)
        telemetry.event('compile', name='x')
        assert seen == []

    def test_broken_subscriber_never_blocks_emission(self):
        rec = telemetry.get_recorder()

        def boom(rec_):
            raise RuntimeError('broken consumer')

        rec.subscribe(boom)
        ev = telemetry.event('compile', name='x')
        assert ev['kind'] == 'compile'
        assert telemetry.events('compile')

    def test_signal_safe_path_does_not_notify(self):
        rec = telemetry.get_recorder()
        seen = []
        rec.subscribe(seen.append)
        rec.event_unlocked('preemption', signum=15)
        assert seen == []       # no user code in a signal context
        assert telemetry.events('preemption')


# ------------------------------------------------------- aggregator --
class TestLiveAggregator:
    def _feed_serve(self, agg=None):
        telemetry.event('serve_step', intervention=1, live=2, batch=2,
                        span=2, decoded=4, admitted=2, finished=0,
                        preempted=1, queued=3, free_blocks=10,
                        total_blocks=21, dur_s=0.02)
        telemetry.event('serve_request', rid='r1', state='done',
                        reason='eos', prompt_len=5, tokens=6,
                        ttft_s=0.10, tpot_s=0.01, preemptions=0,
                        age_s=0.4)
        telemetry.event('serve_request', rid='r2', state='evicted',
                        reason='deadline', prompt_len=5, tokens=2,
                        ttft_s=0.30, tpot_s=0.02, preemptions=1,
                        age_s=0.9)

    def test_routes_serving_events_into_windows(self):
        agg = LiveAggregator().install()
        try:
            self._feed_serve()
            snap = agg.snapshot()
            srv = snap['serving']
            assert srv['ttft_ms']['count'] == 2
            assert srv['ttft_ms']['max'] == pytest.approx(300.0)
            assert srv['tpot_ms']['count'] == 2
            assert srv['decoded_tokens'] == 4
            assert srv['requests_finished'] == 2
            assert srv['preempted'] == 1
            assert srv['finished_by_cause'] == {'deadline': 1,
                                                'eos': 1}
            g = srv['gauges']
            assert g['queued'] == 3 and g['live'] == 2
            # 21 blocks, 1 reserved trash, 10 free -> 10/20 occupied
            assert g['kv_occupancy'] == pytest.approx(0.5)
        finally:
            agg.uninstall()

    def test_steps_flushes_feed_loop_windows(self):
        agg = LiveAggregator().install()
        try:
            telemetry.event('steps', tag='train', n=3,
                            step=[0, 1, 2],
                            step_time_ms=[10.0, 20.0, None])
            pct = agg.snapshot()['steps']['train']
            assert pct['count'] == 2 and pct['max'] == 20.0
        finally:
            agg.uninstall()

    def test_compiles_after_steady_counted(self):
        agg = LiveAggregator().install()
        try:
            telemetry.event('compile', name='warm', dur_s=0.1)
            agg.mark_steady()
            telemetry.event('compile', name='leak', dur_s=0.1)
            c = agg.snapshot()['compiles']
            assert c['total'] == 2 and c['after_steady'] == 1
        finally:
            agg.uninstall()

    def test_trace_store_is_bounded_lru(self):
        agg = LiveAggregator(max_traces=3).install()
        try:
            for i in range(5):
                telemetry.event('serve_trace', rid=f'r{i}',
                                trace=[{'stage': 'queued', 't': 0.0}])
            snap = agg.snapshot()
            assert snap['traced_requests'] == ['r2', 'r3', 'r4']
            assert agg.request_trace('r4')['trace'][0]['stage'] == \
                'queued'
            assert agg.request_trace('r0') is None
        finally:
            agg.uninstall()

    def test_uninstall_stops_updates(self):
        agg = LiveAggregator().install()
        agg.uninstall()
        self._feed_serve()
        assert agg.snapshot()['serving']['requests_finished'] == 0

    def test_prometheus_exposition_format(self):
        agg = LiveAggregator().install()
        try:
            self._feed_serve()
            text = agg.prometheus()
        finally:
            agg.uninstall()
        assert '# TYPE paddle_tpu_serve_ttft_ms gauge' in text
        assert 'paddle_tpu_serve_ttft_ms{quantile="p99"}' in text
        assert 'paddle_tpu_serve_finished_total{cause="eos"} 1' in text
        assert 'paddle_tpu_serve_evictions_total{cause="deadline"} 1' \
            in text
        # clean completions are NOT evictions (alertable family)
        assert 'paddle_tpu_serve_evictions_total{cause="eos"}' \
            not in text
        assert 'paddle_tpu_serve_kv_occupancy 0.5' in text
        # every sample line parses as 'name{labels} value'
        for line in text.strip().splitlines():
            if line.startswith('#'):
                continue
            assert re.match(
                r'^paddle_tpu_[a-z_]+(\{[^}]*\})? \S+$', line), line

    def test_prometheus_label_values_escaped(self):
        agg = LiveAggregator().install()
        try:
            telemetry.event('steps', tag='odd "loop"\\n', n=1,
                            step=[0], step_time_ms=[5.0])
            text = agg.prometheus()
        finally:
            agg.uninstall()
        assert r'loop="odd \"loop\"\\n"' in text


# ------------------------------------------------------ HTTP server --
class TestMetricsServer:
    def test_routes(self):
        agg = LiveAggregator().install()
        srv = MetricsServer(agg, port=0).start()
        try:
            telemetry.event('serve_request', rid='r1', state='done',
                            reason='eos', prompt_len=3, tokens=4,
                            ttft_s=0.05, tpot_s=0.01, preemptions=0,
                            age_s=0.2)
            telemetry.event('serve_trace', rid='r1',
                            trace=[{'stage': 'queued', 't': 0.0}])
            assert json.loads(_get(srv.url + '/healthz'))['ok']
            snap = json.loads(_get(srv.url + '/status.json'))
            assert snap['serving']['ttft_ms']['count'] == 1
            assert 'paddle_tpu_serve_requests_finished_total 1' \
                in _get(srv.url + '/metrics')
            doc = json.loads(_get(srv.url + '/requests/r1'))
            assert doc['trace'][0]['stage'] == 'queued'
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + '/requests/nope')
            assert ei.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + '/bogus')
            assert ei.value.code == 404
        finally:
            srv.stop()
            agg.uninstall()

    def test_resolve_metrics_port_posture(self, monkeypatch):
        monkeypatch.delenv('PADDLE_TPU_METRICS_PORT', raising=False)
        assert resolve_metrics_port(None) is None       # default OFF
        assert resolve_metrics_port(8123) == 8123
        monkeypatch.setenv('PADDLE_TPU_METRICS_PORT', '9100')
        assert resolve_metrics_port(None) == 9100
        assert resolve_metrics_port(False) is None      # False beats env
        monkeypatch.setenv('PADDLE_TPU_METRICS_PORT', 'off')
        assert resolve_metrics_port(None) is None
        monkeypatch.setenv('PADDLE_TPU_METRICS_PORT', '0')
        assert resolve_metrics_port(None) is None


# ---------------------------------------------------------- monitors --
class TestSLOMonitor:
    def _agg(self, monitor):
        agg = LiveAggregator(window_s=0.2).install()
        agg.attach_monitor(monitor)
        return agg

    def _slow_requests(self, n=8, ttft=0.5):
        for i in range(n):
            telemetry.event('serve_request', rid=f's{i}', state='done',
                            reason='eos', prompt_len=3, tokens=4,
                            ttft_s=ttft, tpot_s=0.01, preemptions=0,
                            age_s=1.0)

    def test_ttft_breach_is_latched_edge(self):
        mon = SLOMonitor(ttft_budget_s=0.1, min_samples=4)
        agg = self._agg(mon)
        try:
            self._slow_requests(8, ttft=0.5)
            assert len(telemetry.events('slo_breach')) == 1
            ev = telemetry.events('slo_breach')[0]
            assert ev['what'] == 'ttft_p99'
            assert ev['budget_s'] == pytest.approx(0.1)
            assert ev['observed_s'] == pytest.approx(0.5)
            # still breached -> still exactly one (latched)
            self._slow_requests(4, ttft=0.6)
            assert len(telemetry.events('slo_breach')) == 1
            # window drains, fast traffic re-arms, slow fires again
            time.sleep(0.3)
            self._slow_requests(8, ttft=0.01)
            assert len(telemetry.events('slo_breach')) == 1
            time.sleep(0.3)
            self._slow_requests(8, ttft=0.5)
            assert len(telemetry.events('slo_breach')) == 2
        finally:
            agg.uninstall()

    def test_budget_derives_ttft_threshold(self):
        b = Budget(first_step_s=0.25, step_s=1.0)
        assert b.ttft_budget_s() == pytest.approx(0.25)
        mon = SLOMonitor(budget=b)
        assert mon.ttft_budget_s == pytest.approx(0.25)
        # and the per-request deadline derives from the same machinery
        assert b.request_budget_s(9, span=2) == pytest.approx(
            0.25 + 4 * 1.0)

    def test_deadline_eviction_rate_breach(self):
        mon = SLOMonitor(ttft_budget_s=None, min_samples=4,
                         deadline_evict_frac=0.5)
        agg = self._agg(mon)
        try:
            for i in range(6):
                telemetry.event('serve_request', rid=f'd{i}',
                                state='evicted', reason='deadline',
                                prompt_len=3, tokens=0, ttft_s=None,
                                tpot_s=None, preemptions=0, age_s=2.0)
            evs = telemetry.events('slo_breach')
            assert len(evs) == 1
            assert evs[0]['what'] == 'deadline_evictions'
            assert evs[0]['observed_frac'] == 1.0
        finally:
            agg.uninstall()

    def test_healthy_traffic_never_fires(self):
        mon = SLOMonitor(ttft_budget_s=1.0, min_samples=4)
        agg = self._agg(mon)
        try:
            self._slow_requests(10, ttft=0.05)
            assert telemetry.events('slo_breach') == []
        finally:
            agg.uninstall()


class TestDriftMonitor:
    def test_seeded_drift_injection_fires_exactly_once(self, tmp_path):
        """The ISSUE-13 acceptance: inflate ONE collective's observed
        us -> exactly one drift_detected, visible in /status.json AND
        in run_report (timeline + serving section)."""
        telemetry.enable(str(tmp_path))
        agg = LiveAggregator().install()
        agg.attach_monitor(DriftMonitor(ratio_band=4.0))
        srv = MetricsServer(agg, port=0).start()
        try:
            # healthy collective: inside the band, never fires
            for _ in range(3):
                telemetry.event('collective_observed',
                                op='all-gather', instr='all-gather.1',
                                us=110.0, predicted_us=100.0, calls=1,
                                wire_bytes=1024, phases=7)
            assert telemetry.events('drift_detected') == []
            # the injection: observed us 9x the prediction, repeatedly
            for _ in range(5):
                telemetry.event('collective_observed',
                                op='all-reduce', instr='all-reduce.3',
                                us=900.0, predicted_us=100.0, calls=1,
                                wire_bytes=4096, phases=14)
            evs = telemetry.events('drift_detected')
            assert len(evs) == 1            # latched: an edge, not a
            ev = evs[0]                     # firehose
            assert ev['cause'] == 'us_ratio'
            assert ev['op'] == 'all-reduce'
            assert ev['us_ratio'] > 4.0
            # visible live
            snap = json.loads(_get(srv.url + '/status.json'))
            kinds = [a['kind'] for a in snap['alerts']]
            assert kinds == ['drift_detected']
        finally:
            srv.stop()
            agg.uninstall()
            telemetry.disable()
        # ...and post-mortem: run_report picks it up from the JSONL
        out = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'),
             str(tmp_path), '--json'],
            capture_output=True, text=True)
        rep = json.loads(out.stdout)
        drifts = [r for r in rep['timeline']
                  if r['kind'] == 'drift_detected']
        assert len(drifts) == 1 and drifts[0]['us_ratio'] > 4.0

    def test_post_steady_compile_fires_once_per_name(self):
        agg = LiveAggregator().install()
        agg.attach_monitor(DriftMonitor())
        try:
            telemetry.event('compile', name='warmup', dur_s=1.0)
            assert telemetry.events('drift_detected') == []
            agg.mark_steady()
            telemetry.event('compile', name='leaked.bucket', dur_s=1.0)
            telemetry.event('compile', name='leaked.bucket', dur_s=1.0)
            evs = telemetry.events('drift_detected')
            assert len(evs) == 1
            assert evs[0]['cause'] == 'post_steady_compile'
            assert evs[0]['name'] == 'leaked.bucket'
        finally:
            agg.uninstall()


# ----------------------------------------- engine live plane (e2e) --
class TestEngineLivePlane:
    def test_scrape_during_run_changes_no_numerics(self):
        """ISSUE-13 acceptance: a server-on engine scraped throughout
        its run produces BIT-EXACT token streams vs a server-off
        engine on the same requests, with the same compile count."""
        model = _tiny_model()
        eng_off = ServingEngine(model, _tiny_config())
        eng_off.run(_tiny_load(model))
        ref = {r.rid: list(r.tokens)
               for r in eng_off.scheduler.finished}
        compiles_ref = eng_off.compile_count

        eng_on = ServingEngine(model, _tiny_config(),
                               serve_metrics_port=0)
        url = eng_on.metrics_server.url
        scrapes, errors = [], []
        stop = threading.Event()

        def scraper():
            while not stop.wait(0.02):
                try:
                    _get(url + '/metrics')
                    scrapes.append(json.loads(
                        _get(url + '/status.json')))
                except Exception as e:      # pragma: no cover
                    errors.append(repr(e))

        th = threading.Thread(target=scraper, daemon=True)
        th.start()
        try:
            eng_on.run(_tiny_load(model))
        finally:
            stop.set()
            th.join(timeout=10)
        got = {r.rid: list(r.tokens)
               for r in eng_on.scheduler.finished}
        try:
            assert not errors
            assert scrapes                  # scraped while running
            assert got == ref               # bit-exact
            assert eng_on.compile_count == compiles_ref
            snap = json.loads(_get(url + '/status.json'))
            srv = snap['serving']
            assert srv['ttft_ms'].get('count')
            assert srv['tpot_ms'].get('count')
            assert 'kv_occupancy' in srv['gauges']
            assert srv['decoded_tokens'] == eng_on.decoded_tokens
        finally:
            eng_on.close()
        assert eng_on.metrics_server is None    # close is clean
        with pytest.raises(Exception):
            _get(url + '/healthz')

    def test_request_trace_view_and_serve_trace_events(self):
        model = _tiny_model()
        eng = ServingEngine(model, _tiny_config(),
                            serve_metrics_port=0)
        try:
            eng.run(_tiny_load(model, n=3))
            traces = telemetry.events('serve_trace')
            assert len(traces) == 3
            rid = traces[0]['rid']
            stages = [r['stage'] for r in traces[0]['trace']]
            # the full lifecycle, in order
            assert stages[0] == 'queued'
            assert stages[1] == 'admitted'
            assert stages[2] == 'prefill'
            assert stages[3] == 'first_token'
            assert 'decode_span' in stages[4:]
            assert stages[-1] in ('finished', 'evicted')
            # joinable by rid with serve_request
            assert rid in {e['rid']
                           for e in telemetry.events('serve_request')}
            # and served over HTTP
            doc = json.loads(_get(
                eng.metrics_server.url + f'/requests/{rid}'))
            assert [r['stage'] for r in doc['trace']] == stages
            # the admitted row carries its bucket tag, finish its cause
            admitted = traces[0]['trace'][1]
            assert admitted['bucket'] in (4, 8)
            assert traces[0]['trace'][-1]['cause'] in (
                'eos', 'max_tokens', 'deadline')
        finally:
            eng.close()

    def test_engine_timeout_evictions_emit_telemetry(self):
        """run(timeout_s=) evictions go through the same serve_request
        / serve_trace emission as every other finish — overload is
        exactly when the evidence matters."""
        model = _tiny_model()
        eng = ServingEngine(model, _tiny_config())
        seen = []
        # the full trails are copied into events only where something
        # reads the stream (a subscriber here, the live plane or a
        # JSONL writer in a deployment)
        telemetry.get_recorder().subscribe(seen.append)
        for r in _tiny_load(model, n=3):
            eng.submit(r.prompt, max_new_tokens=4)
        eng.run((), timeout_s=0.0)
        evs = telemetry.events('serve_request')
        assert len(evs) == 3
        assert {e['reason'] for e in evs} == {'engine_timeout'}
        assert len(telemetry.events('serve_trace')) == 3
        assert sum(e['kind'] == 'serve_trace' for e in seen) == 3

    def test_no_consumer_no_serve_trace_but_rows_stay(self):
        """Nothing attached: no serve_trace event is built and no
        frag_report runs per intervention, the flight ring keeps
        serve_request and serve_step with the cheap fields, and the
        rows stay on the Request for _live_trace."""
        model = _tiny_model()
        eng = ServingEngine(model, _tiny_config())
        calls = []
        frag = eng.cache.frag_report
        eng.cache.frag_report = lambda: calls.append(1) or frag()
        load = _tiny_load(model, n=3)
        eng.run(load)
        assert not telemetry.streaming()
        assert telemetry.events('serve_trace') == []
        assert len(telemetry.events('serve_request')) == 3
        steps = telemetry.events('serve_step')
        assert steps and not calls
        assert all('kv_frag_frac' not in e for e in steps)
        assert all(e['kv_high_water'] >= 1 and 'free_blocks' in e
                   for e in steps)
        rid = load[0].rid
        stages = [r['stage'] for r in eng._live_trace(rid)]
        assert stages[:2] == ['queued', 'admitted']
        rec = load[0].record(0.0)
        assert rec['submit_late_s'] >= 0
        assert rec['queue_wait_s'] >= rec['submit_late_s']

    def test_prefill_only_tokens_reach_the_live_plane(self):
        """max_new_tokens=1 requests finish AT prefill — no decode
        serve_step ever fires, but the carried first-token counts
        must still reach the aggregator (and run_report's sum)."""
        model = _tiny_model()
        eng = ServingEngine(model, _tiny_config(),
                            serve_metrics_port=0)
        try:
            prompts = _tiny_load(model, n=3)
            for r in prompts:
                eng.submit(r.prompt, max_new_tokens=1)
            while eng.scheduler.queue or eng.scheduler.running:
                eng.step()
            assert eng.decoded_tokens == 3
            snap = json.loads(_get(
                eng.metrics_server.url + '/status.json'))
            assert snap['serving']['decoded_tokens'] == 3
            # run_report's accounting identity holds too
            steps = telemetry.events('serve_step')
            total = sum((e.get('decoded') or 0)
                        + (e.get('prefilled') or 0)
                        - (e.get('discarded') or 0) for e in steps)
            assert total == 3
        finally:
            eng.close()

    def test_default_off_and_close_idempotent(self):
        model = _tiny_model()
        eng = ServingEngine(model, _tiny_config())
        assert eng.metrics_server is None and eng.live is None
        eng.close()
        eng.close()


# ---------------------------------------------- sync-free guarantee --
class TestLiveStaysSyncFree:
    def test_trainer_loop_with_live_enabled_no_host_transfer(self):
        """ISSUE-13 acceptance: live.py enabled on a NON-serving
        trainer loop adds zero device→host transfers per step — the
        aggregator consumes only the buffered flushes."""
        agg = LiveAggregator().install()
        telemetry.enable(None)
        try:
            paddle.seed(0)
            net = nn.Linear(4, 2)
            model = paddle.hapi.Model(net)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters())
            model.prepare(optimizer=opt, loss=nn.MSELoss())
            model._check_finite_steps = False
            rs = np.random.RandomState(0)
            x = rs.randn(8, 4).astype('float32')
            y = rs.randn(8, 2).astype('float32')
            model.train_batch(x, y)         # compile outside the guard
            acc = telemetry.step_accumulator('liveguard')
            with jax.transfer_guard_device_to_host('disallow'):
                for i in range(8):
                    t0 = time.perf_counter()
                    loss, _ = model.train_batch(x, y)
                    acc.observe(step=i,
                                step_time_s=time.perf_counter() - t0,
                                loss=loss)
            acc.flush()         # the one sync, at the boundary
            pct = agg.snapshot()['steps']['liveguard']
            assert pct['count'] == 8
        finally:
            agg.uninstall()


# -------------------------------------------- run_report integration --
class TestRunReportServing:
    def test_serving_section_joined_from_events(self, tmp_path):
        telemetry.enable(str(tmp_path))
        model = _tiny_model()
        eng = ServingEngine(model, _tiny_config())
        eng.run(_tiny_load(model, n=4))
        telemetry.disable()
        out = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'),
             str(tmp_path), '--json'],
            capture_output=True, text=True)
        assert out.returncode == 0, out.stderr
        rep = json.loads(out.stdout)
        assert 'serving' in rep             # schema gained the key
        sv = rep['serving']
        assert sv['requests'] == 4
        assert sv['completed'] + sv['evicted'] == 4
        assert sv['ttft_ms']['steps'] == 4
        assert sv['decoded_tokens'] > 0
        assert sv['interventions'] > 0
        assert sum(sv['by_cause'].values()) == 4
        assert len(sv['request_timeline']) == 4
        row = sv['request_timeline'][0]
        assert {'rid', 'state', 'reason', 'prompt_len',
                'tokens'} <= set(row)
        # lifecycle traces joined by rid
        assert set(sv['traces']) == {r['rid']
                                     for r in sv['request_timeline']}
        # human render has the section too
        out2 = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'),
             str(tmp_path)],
            capture_output=True, text=True)
        assert '-- serving --' in out2.stdout
        assert 'TTFT' in out2.stdout

    def test_no_serving_events_keeps_section_null(self, tmp_path):
        telemetry.enable(str(tmp_path))
        telemetry.event('compile', name='x', dur_s=0.1)
        telemetry.disable()
        out = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'),
             str(tmp_path), '--json'],
            capture_output=True, text=True)
        rep = json.loads(out.stdout)
        assert rep['serving'] is None


# --------------------------------------------- recorder meta-tests --
_EMIT_RE = re.compile(
    r"(?:\.event(?:_unlocked)?|\b_event)\(\s*['\"]([a-z_]+)['\"]")


class TestEventKindsMeta:
    def test_every_emitted_kind_is_declared(self):
        """Grep every emission site under paddle_tpu/ for a literal
        first argument: each kind MUST be documented in EVENT_KINDS.
        (Dynamic-kind emitters like the watchdog's _emit pass through
        variables and are covered by their own tests.)"""
        pkg = os.path.join(_REPO, 'paddle_tpu')
        emitted = {}
        for root, _dirs, files in os.walk(pkg):
            for f in files:
                if not f.endswith('.py'):
                    continue
                path = os.path.join(root, f)
                with open(path) as fh:
                    src = fh.read()
                for m in _EMIT_RE.finditer(src):
                    emitted.setdefault(m.group(1), set()).add(
                        os.path.relpath(path, _REPO))
        assert emitted, 'meta-test regex matched no emission sites'
        undeclared = {k: sorted(v) for k, v in emitted.items()
                      if k not in EVENT_KINDS}
        assert not undeclared, (
            f'event kinds emitted but not declared in EVENT_KINDS: '
            f'{undeclared}')

    def test_new_kinds_documented(self):
        for kind in ('serve_trace', 'slo_breach', 'drift_detected',
                     'crash', 'straggler_suspect', 'rank_divergence',
                     'collective_mismatch'):
            assert kind in EVENT_KINDS

    def test_every_kind_rendered_or_ignore_listed(self):
        """The CONSUMPTION side of the vocabulary: every declared
        EVENT_KINDS entry must either be read by run_report's
        analyze() (RENDERED_KINDS) or sit on its explicit, reasoned
        ignore list — an event can never again be emitted and
        silently dropped (the PR-12 serve_step/serve_request bug,
        prevented structurally this time)."""
        sys.path.insert(0, os.path.join(_REPO, 'tools'))
        try:
            import run_report
        finally:
            sys.path.pop(0)
        rendered = set(run_report.RENDERED_KINDS)
        ignored = set(run_report.IGNORED_KINDS)
        declared = set(EVENT_KINDS)
        uncovered = declared - rendered - ignored
        assert not uncovered, (
            f'EVENT_KINDS entries neither rendered by run_report nor '
            f'ignore-listed with a reason: {sorted(uncovered)} — '
            'either consume them in analyze() or add them to '
            'IGNORED_KINDS saying why')
        # the coverage sets must not rot either: no unknown kinds, no
        # kind claiming both dispositions, and every ignore entry
        # carries a non-empty reason
        assert not (rendered - declared), (rendered - declared)
        assert not (ignored - declared), (ignored - declared)
        assert not (rendered & ignored), (rendered & ignored)
        for kind, reason in run_report.IGNORED_KINDS.items():
            assert reason and reason.strip(), kind

        # and RENDERED_KINDS must be honest: each rendered kind is
        # actually mentioned in analyze()'s source
        import inspect
        src = inspect.getsource(run_report.analyze)
        src += ' '.join(run_report.RESILIENCE_KINDS)  # timeline set
        for kind in rendered:
            assert kind in src, (
                f'{kind} claimed as rendered but analyze() never '
                'references it')

    def test_serve_request_field_schema(self):
        """The serve_request event contract run_report and the live
        plane join on."""
        model = _tiny_model()
        eng = ServingEngine(model, _tiny_config())
        eng.run(_tiny_load(model, n=2))
        evs = telemetry.events('serve_request')
        assert len(evs) == 2
        required = {'rid', 'state', 'reason', 'prompt_len', 'tokens',
                    'ttft_s', 'tpot_s', 'preemptions', 'age_s'}
        for ev in evs:
            assert required <= set(ev), ev
            assert ev['state'] in ('done', 'evicted')
            assert isinstance(ev['rid'], str)
            assert ev['tokens'] >= 1
            assert ev['ttft_s'] is None or ev['ttft_s'] >= 0
