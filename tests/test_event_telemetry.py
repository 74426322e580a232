"""Unified run telemetry (paddle_tpu.telemetry).

Recorder/span/counter semantics, the sync-free flush-interval step
path (proven with a device→host transfer guard AND the analysis
host-sync rule over the telemetry-enabled hapi step), flight-recorder
dumps on simulated preemption and NaN rollback (`faultinject`), and
the JSONL → tools/run_report.py round trip with a schema check.

NOTE this file must sort alphabetically before test_host_embedding.py:
the seed's tier-1 run aborts there (XLA compiler crash) and later
files never execute.
"""
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.telemetry import (
    Recorder, StepAccumulator, StepTimer, percentiles)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Each test gets a virgin process-global recorder."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _mse_model(lr=0.1):
    paddle.seed(0)
    net = nn.Linear(4, 2)
    model = paddle.hapi.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=lr,
                               parameters=net.parameters())
    model.prepare(optimizer=opt, loss=nn.MSELoss())
    return model


# ---------------------------------------------------------- recorder --
class TestRecorder:
    def test_counters_and_gauges(self):
        r = Recorder()
        r.add('x')
        r.add('x', 2)
        r.set_gauge('g', 7.5)
        assert r.counters['x'] == 3
        assert r.gauges['g'] == 7.5

    def test_event_ring_is_bounded(self):
        r = Recorder(max_events=4)
        for i in range(9):
            r.event('compile', i=i)
        evs = r.events()
        assert len(evs) == 4
        assert [e['i'] for e in evs] == [5, 6, 7, 8]

    def test_event_fields_and_filter(self):
        r = Recorder()
        r.event('retrace', name='f', variants=2)
        r.event('compile', name='g')
        evs = r.events('retrace')
        assert len(evs) == 1
        e = evs[0]
        assert e['name'] == 'f' and e['variants'] == 2
        assert e['ts'] > 0 and e['t'] >= 0

    def test_span_nesting_and_stats(self):
        r = Recorder()
        with r.span('outer', rid='r7') as outer:
            with r.span('inner', target='x') as inner:
                pass
            with r.span('other', rid='r8') as other:
                pass
        assert r.span_stats['outer']['count'] == 1
        assert r.span_stats['inner']['count'] == 1
        assert r.span_stats['outer']['total_s'] >= \
            r.span_stats['inner']['total_s']
        by_name = {e['name']: e for e in r.events('span')}
        inner_ev = by_name['inner']
        # nesting gives parent_id; a request's spans share its rid
        assert inner_ev['parent_id'] == by_name['outer']['id'] \
            == outer.id
        assert by_name['outer']['parent_id'] is None
        assert inner_ev['rid'] == by_name['outer']['rid'] == 'r7'
        assert by_name['other']['rid'] == 'r8'
        assert by_name['other']['parent_id'] == outer.id
        assert len({outer.id, inner.id, other.id}) == 3
        assert inner_ev['target'] == 'x'
        for sp, ev in ((outer, by_name['outer']), (inner, inner_ev)):
            assert 0 <= sp.start <= sp.end
            assert ev['start'] == pytest.approx(sp.start, abs=1e-5)
            assert ev['end'] == pytest.approx(sp.end, abs=1e-5)
            assert ev['dur_s'] == pytest.approx(sp.end - sp.start,
                                                abs=1e-5)
        assert outer.start <= inner.start and inner.end <= outer.end

    def test_span_off_leaves_nothing_in_memory(self):
        """Telemetry not enabled (the default, and how the benchmark
        measures): a span is its profiler annotation and nothing else —
        ring, counters and span_stats unchanged."""
        r = telemetry.get_recorder()
        assert not telemetry.enabled()
        before = (len(r.events()), dict(r.counters), dict(r.span_stats))
        with telemetry.span('serve.step', rid='r1') as sp:
            with telemetry.span('serve.plan') as child:
                pass
        assert (len(r.events()), dict(r.counters),
                dict(r.span_stats)) == before
        assert r._span_stack() == []
        assert sp.id is None and sp.start is None and sp.end is None
        assert child.parent_id is None and child.rid is None

    def test_span_enabled_records_and_streams(self, tmp_path):
        """enable() turns the same call sites into records: span
        events with start/end/id/parent_id/rid in the ring and the
        JSONL stream, and span_stats for the flight dump."""
        telemetry.enable(str(tmp_path))
        with telemetry.span('serve.step', rid='r1'):
            with telemetry.span('serve.plan'):
                pass
        evs = {e['name']: e for e in telemetry.events('span')}
        assert evs['serve.plan']['parent_id'] == evs['serve.step']['id']
        assert evs['serve.plan']['rid'] == 'r1'
        assert evs['serve.plan']['start'] <= evs['serve.plan']['end']
        r = telemetry.get_recorder()
        assert r.span_stats['serve.step']['count'] == 1
        telemetry.disable()
        rows = [json.loads(line) for line in
                (tmp_path / 'telemetry-r0.jsonl').read_text().splitlines()]
        spans = [x for x in rows if x['kind'] == 'span']
        assert {x['name'] for x in spans} == {'serve.step', 'serve.plan'}
        assert all({'start', 'end', 'id', 'parent_id', 'rid'} <= set(x)
                   for x in spans)

    def test_record_event_is_the_same_span(self):
        """The reference's RecordEvent name and telemetry.span are one
        object: one span implementation in the package."""
        from paddle_tpu import profiler
        assert profiler.RecordEvent is telemetry.span is telemetry.Span
        telemetry.enable()
        with profiler.RecordEvent('user_block'):
            pass
        assert [e['name'] for e in telemetry.events('span')] \
            == ['user_block']

    def test_event_unlocked_is_ring_only(self, tmp_path):
        telemetry.enable(str(tmp_path))
        r = telemetry.get_recorder()
        r.event_unlocked('preemption', signum=15)
        assert r.events('preemption')
        # unlocked events skip the JSONL writer (signal-safety)
        stream = (tmp_path / f'telemetry-r0.jsonl').read_text()
        assert 'preemption' not in stream

    def test_dump_flight_atomic_and_complete(self, tmp_path):
        r = Recorder()
        r.add('retrace.count', 3)
        with r.span('compile'):
            pass
        r.event('nan_skip', strikes=1)
        p = r.dump_flight(str(tmp_path / 'sub' / 'flightrec-5.json'))
        doc = json.load(open(p))
        assert doc['version'] == 1
        assert doc['counters']['retrace.count'] == 3
        assert 'compile' in doc['span_stats']
        assert any(e['kind'] == 'nan_skip' for e in doc['events'])
        assert not os.path.exists(p + '.tmp')

    def test_hard_off_disables_everything(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_TELEMETRY', '0')
        assert not telemetry.active()
        assert telemetry.enable('/nonexistent') is None
        assert telemetry.event('compile') is None
        assert telemetry.step_accumulator() is None
        assert telemetry.dump_flight('/nonexistent/x.json') is None


# --------------------------------------------------- step accumulator --
class TestStepAccumulator:
    def test_flush_interval_batches_events(self):
        r = Recorder()
        acc = StepAccumulator(tag='t', flush_interval=3, recorder=r)
        for i in range(7):
            acc.observe(step=i, step_time_s=0.001, loss=float(i))
        assert len(r.events('steps')) == 2          # 3 + 3 buffered
        assert len(acc) == 1
        acc.flush()
        evs = r.events('steps')
        assert len(evs) == 3
        assert [e['n'] for e in evs] == [3, 3, 1]
        assert evs[0]['loss'] == [0.0, 1.0, 2.0]
        assert evs[0]['step_lo'] == 0 and evs[0]['step_hi'] == 2
        assert r.counters['steps.count'] == 7

    def test_device_scalars_stay_lazy_until_flush(self):
        """The sync-free contract: observe() buffers DEVICE scalars
        without any device→host transfer; only flush() reads back."""
        r = Recorder()
        acc = StepAccumulator(tag='t', flush_interval=100, recorder=r)
        losses = [jnp.asarray(1.5 * i) for i in range(6)]
        with jax.transfer_guard_device_to_host('disallow'):
            for i, lv in enumerate(losses):
                acc.observe(step=i, step_time_s=0.001, loss=lv)
        acc.flush()     # the one sync, outside the guarded region
        ev = r.events('steps')[0]
        np.testing.assert_allclose(ev['loss'],
                                   [1.5 * i for i in range(6)])

    def test_step_times_feed_reservoir(self):
        r = Recorder()
        acc = StepAccumulator(tag='t', flush_interval=2, recorder=r)
        acc.observe(step=0, step_time_s=0.010)
        acc.observe(step=1, step_time_s=0.030)
        s = percentiles(r.step_times('t'))
        assert s['steps'] == 2
        assert s['mean_ms'] == pytest.approx(20.0)

    def test_percentiles_shape(self):
        s = percentiles([0.001] * 10)
        assert set(s) == {'steps', 'mean_ms', 'p50_ms', 'p90_ms',
                          'p99_ms', 'max_ms'}
        assert percentiles([]) == {}


# --------------------------------------------------------- step timer --
class TestStepTimerUnified:
    def test_single_implementation_everywhere(self):
        from paddle_tpu.profiler import StepTimer as A
        from paddle_tpu.utils.profiler import StepTimer as B
        assert A is StepTimer and B is StepTimer

    def test_window_and_summary(self):
        t = StepTimer(window=3, record=False)
        for _ in range(5):
            t.start()
            t.stop()
        assert len(t._times) == 3
        assert set(t.summary()) == {'mean_ms', 'p50_ms', 'p90_ms',
                                    'max_ms', 'steps'}

    def test_stop_feeds_recorder_reservoir(self):
        t = StepTimer(window=5, tag='mytimer')
        t.start()
        t.stop()
        assert len(telemetry.get_recorder().step_times('mytimer')) == 1


# ------------------------------------------------ emission points -----
class TestEmissionPoints:
    def test_note_retrace_emits_event_and_counter(self):
        from paddle_tpu.analysis import note_retrace
        note_retrace('fake_step', 1)     # first variant: not a retrace
        assert telemetry.events('retrace') == []
        note_retrace('fake_step', 2)
        note_retrace('fake_step', 3)
        evs = telemetry.events('retrace')
        assert [e['variants'] for e in evs] == [2, 3]
        assert telemetry.get_recorder().counters['retrace.count'] == 2

    def test_lint_emit_lands_findings(self):
        from paddle_tpu import analysis
        rep = analysis.LintReport(
            [analysis.Finding('host-sync', analysis.HIGH, 'x',
                              file='f.py', line=3)], name='t')
        with pytest.warns(analysis.LintWarning):
            analysis.emit(rep, 'warn')
        evs = telemetry.events('lint_finding')
        assert evs and evs[0]['rule'] == 'host-sync'
        assert telemetry.get_recorder().counters['lint.high'] == 1

    def test_nan_sentinel_events(self):
        from paddle_tpu.resilience import NanSentinel
        s = NanSentinel(patience=2, max_rollbacks=2)
        s.observe(loss=float('nan'))
        s.observe(loss=float('nan'))
        kinds = [e['kind'] for e in telemetry.events()]
        assert kinds.count('nan_skip') == 1
        assert kinds.count('nan_rollback') == 1

    def test_checkpoint_save_restore_events(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import CheckpointManager
        telemetry.enable()      # spans keep records only when enabled
        tree = {'w': jnp.arange(8.0), 'step': jnp.asarray(3)}
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(tree, 3)
        _, got = mgr.restore(tree)
        assert got == 3
        kinds = [e['kind'] for e in telemetry.events()]
        assert 'checkpoint_save' in kinds
        assert 'checkpoint_commit' in kinds
        ev = telemetry.events('checkpoint_save')[0]
        assert ev['step'] == 3 and ev['async_save'] is False
        spans = [e for e in telemetry.events('span')
                 if e['name'] == 'checkpoint_restore']
        assert spans and spans[0]['step'] == 3

    def test_dataloader_host_wait_counter(self):
        from paddle_tpu.io import DataLoader, TensorDataset
        xs = paddle.to_tensor(np.arange(32, dtype='float32')
                              .reshape(8, 4))
        loader = DataLoader(TensorDataset([xs]), batch_size=2)
        n = sum(1 for _ in loader)
        assert n == 4
        c = telemetry.get_recorder().counters
        assert c['io.dataloader.batches'] == 4
        assert c['io.dataloader.wait_s'] >= 0

    def test_hapi_fit_emits_compile_steps_and_span(self, tmp_path):
        telemetry.enable(str(tmp_path), flush_interval=4)
        model = _mse_model()
        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 6
        model.fit(data, epochs=1, verbose=0)
        kinds = [e['kind'] for e in telemetry.events()]
        assert 'compile' in kinds
        assert 'steps' in kinds
        assert any(e['name'] == 'fit'
                   for e in telemetry.events('span'))
        ev = telemetry.events('steps')[0]
        assert ev['n'] == 4 and len(ev['loss']) == 4
        assert all(t is not None for t in ev['step_time_ms'])


# -------------------------------------------- sync-free guard (hapi) --
class TestHapiStepLoopStaysSyncFree:
    def test_telemetry_enabled_step_loop_no_host_transfer(self):
        """Acceptance gate: with telemetry enabled at the default
        flush interval, the sync-free hapi step path plus telemetry
        observe() performs ZERO device→host transfers per step."""
        telemetry.enable(None)      # default flush_interval=32
        model = _mse_model()
        model._check_finite_steps = False   # NanGuard(enable=False)
        rs = np.random.RandomState(0)
        x = rs.randn(8, 4).astype('float32')
        y = rs.randn(8, 2).astype('float32')
        model.train_batch(x, y)     # compile outside the guard
        acc = telemetry.step_accumulator('guard')
        import time
        with jax.transfer_guard_device_to_host('disallow'):
            for i in range(8):
                t0 = time.perf_counter()
                loss, _ = model.train_batch(x, y)
                acc.observe(step=i, step_time_s=time.perf_counter() - t0,
                            loss=loss)
        acc.flush()                 # the one sync, at the boundary
        ev = telemetry.events('steps')[-1]
        assert ev['n'] == 8
        assert np.isfinite(ev['loss']).all()

    def test_train_step_passes_host_sync_audit(self):
        """The jaxpr the telemetry-enabled loop compiles contains no
        host callbacks (the analysis host-sync rule stays clean)."""
        from paddle_tpu import analysis
        telemetry.enable(None)
        model = _mse_model()
        rs = np.random.RandomState(0)
        arrays = [jnp.asarray(rs.randn(8, 4).astype('float32')),
                  jnp.asarray(rs.randn(8, 2).astype('float32'))]
        st = model._get_fstate()
        step_fn = model._build_train_step(1)
        report = analysis.lint(
            step_fn, st['params'], st['buffers'], st['opt'],
            jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.float32), *arrays,
            donate_argnums=(0, 1, 2), source=False,
            name='telemetry-guard')
        assert not [f for f in report if f.rule == 'host-sync'], \
            report.render()


# -------------------------------------- buffered progress callbacks --
class TestBufferedCallbacks:
    def test_visualdl_buffers_device_scalars(self, tmp_path):
        """The per-step float() the old VisualDL paid is gone: device
        scalars buffer un-materialized (no transfer under the guard)
        and flush only at log_freq."""
        from paddle_tpu.hapi.callbacks import VisualDL
        vdl = VisualDL(log_dir=str(tmp_path), log_freq=4)
        losses = [jnp.asarray(float(i)) for i in range(4)]
        with jax.transfer_guard_device_to_host('disallow'):
            for i in range(3):
                vdl.on_train_batch_end(i, {'loss': losses[i]})
        assert not os.path.exists(
            os.path.join(str(tmp_path), 'events.jsonl'))
        vdl.on_train_batch_end(3, {'loss': losses[3]})  # flush point
        vdl.on_train_end({})
        lines = [json.loads(l) for l in
                 open(os.path.join(str(tmp_path), 'events.jsonl'))]
        assert [r['value' if 'value' in r else 'loss']
                for r in lines] == [0.0, 1.0, 2.0, 3.0]
        assert [r['step'] for r in lines] == [1, 2, 3, 4]
        # each record also rode the telemetry stream
        assert len(telemetry.events('scalar')) == 4

    def test_visualdl_flushes_at_epoch_and_eval_end(self, tmp_path):
        from paddle_tpu.hapi.callbacks import VisualDL
        vdl = VisualDL(log_dir=str(tmp_path), log_freq=100)
        vdl.on_train_batch_end(0, {'loss': 1.0})
        vdl.on_epoch_end(0, {})
        vdl.on_eval_end({'loss': 2.0})
        vdl.on_train_end({})
        lines = [json.loads(l) for l in
                 open(os.path.join(str(tmp_path), 'events.jsonl'))]
        assert [r['tag'] for r in lines] == ['train', 'eval']

    def test_fit_with_visualdl_still_writes_events(self, tmp_path):
        from paddle_tpu.hapi.callbacks import VisualDL
        model = _mse_model()
        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 4
        model.fit(data, epochs=1, verbose=0,
                  callbacks=[VisualDL(log_dir=str(tmp_path / 'vdl'),
                                      log_freq=2)])
        assert os.path.exists(str(tmp_path / 'vdl' / 'events.jsonl'))


# ------------------------------------------------- flight recorder ----
@pytest.mark.faultinject
class TestFlightRecorderDumps:
    def test_preemption_dumps_next_to_checkpoints(self, tmp_path):
        """SIGTERM preemption during fit leaves flightrec-<step>.json
        in the save_dir, with the preemption event inside."""
        from paddle_tpu.resilience import shutdown as sd
        from paddle_tpu.resilience import PREEMPTED_EXIT_CODE
        from paddle_tpu.hapi.callbacks import Callback

        class PreemptAt(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 1:
                    sd.install_shutdown().request(signal.SIGTERM)

        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 4
        model = _mse_model()
        try:
            with pytest.raises(SystemExit) as ei:
                model.fit(data, epochs=2, verbose=0,
                          save_dir=str(tmp_path),
                          callbacks=[PreemptAt()])
            assert ei.value.code == PREEMPTED_EXIT_CODE
        finally:
            sd.clear_shutdown()
        recs = sorted(tmp_path.glob('flightrec-*.json'))
        assert recs, list(tmp_path.iterdir())
        doc = json.load(open(recs[0]))
        kinds = [e['kind'] for e in doc['events']]
        assert 'preemption' in kinds

    def test_parallel_nan_rollback_dumps_in_ckpt_dir(self, tmp_path):
        """ParallelTrainer's sentinel rollback writes the flight
        recorder next to the checkpoint it restores."""
        from paddle_tpu.parallel import ParallelTrainer
        from paddle_tpu.distributed import env as denv
        denv.set_mesh(None)
        paddle.seed(0)
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        mse = nn.MSELoss()
        tr = ParallelTrainer(net, opt, lambda out, y: mse(out, y),
                             nan_guard=True, nan_patience=1,
                             nan_max_rollbacks=3)
        rs = np.random.RandomState(0)
        x = rs.randn(8, 4).astype('float32')
        y = rs.randn(8, 2).astype('float32')
        tr.step(x, y)
        tr.save_checkpoint(str(tmp_path), async_save=False)
        xbad = x.copy()
        xbad[0, 0] = np.nan
        tr.step(xbad, y)            # strike -> rollback -> restore
        recs = sorted(tmp_path.glob('flightrec-*.json'))
        assert recs
        doc = json.load(open(recs[0]))
        kinds = [e['kind'] for e in doc['events']]
        assert 'nan_rollback' in kinds
        assert 'checkpoint_save' in kinds
        # training continues finite after the rollback
        loss = tr.step(x, y)
        assert np.isfinite(float(np.asarray(loss)))

    def test_crash_hook_dumps(self, tmp_path):
        """An unhandled exception with telemetry enabled leaves a
        crash dump (exercised via the installed excepthook)."""
        telemetry.enable(str(tmp_path))
        telemetry.event('compile', name='x')
        hook = sys.excepthook
        try:
            hook(ValueError, ValueError('boom'), None)
        except Exception:
            pass
        recs = sorted(tmp_path.glob('flightrec-crash-*.json'))
        assert recs
        doc = json.load(open(recs[0]))
        assert any(e['kind'] == 'crash' for e in doc['events'])


# ------------------------------------------------ run_report CLI ------
class TestRunReport:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable,
             os.path.join(_REPO, 'tools', 'run_report.py'), *args],
            capture_output=True, text=True, timeout=120)

    def _make_run(self, d):
        """A miniature faultinject run: train steps + retrace + NaN
        skip/rollback + checkpoint + preemption, streamed to JSONL."""
        from paddle_tpu.analysis import note_retrace
        from paddle_tpu.resilience import NanSentinel
        from paddle_tpu.distributed.checkpoint import CheckpointManager
        telemetry.enable(d, flush_interval=4)
        model = _mse_model()
        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 8
        model.fit(data, epochs=1, verbose=0)
        note_retrace('report_step', 2)
        s = NanSentinel(patience=1, max_rollbacks=2)
        s.observe(loss=float('nan'))        # -> nan_rollback event
        mgr = CheckpointManager(os.path.join(d, 'ckpt'),
                                async_save=False)
        mgr.save({'w': jnp.arange(4.0)}, 1)
        telemetry.event('preemption', signum=15, step=8)
        telemetry.dump_flight(os.path.join(d, 'flightrec-8.json'))
        telemetry.disable()

    def test_json_schema_and_reconstruction(self, tmp_path):
        d = str(tmp_path)
        self._make_run(d)
        p = self._run(d, '--json')
        assert p.returncode == 0, p.stderr
        rep = json.loads(p.stdout)
        # schema contract for bench/CI consumers
        for key in ('schema_version', 'hosts', 'steps', 'split',
                    'compile', 'retraces', 'timeline', 'spans',
                    'total_steps', 'lint_findings', 'sources'):
            assert key in rep, key
        assert rep['schema_version'] == 1
        assert rep['hosts'] == [0]
        # step-time percentiles reconstructed
        st = rep['steps']['train']
        assert st['count'] == 8
        assert st['p50_ms'] > 0 and st['p99_ms'] >= st['p50_ms']
        # device-step vs host-wait split present
        assert 'train' in rep['split']
        assert rep['split']['train']['host_wait_ms'] >= 0
        # compile total + retrace count
        assert rep['compile']['count'] >= 1
        assert rep['compile']['total_s'] > 0
        assert rep['retraces']['count'] == 1
        # the full resilience timeline, in order
        kinds = [row['kind'] for row in rep['timeline']]
        assert 'nan_rollback' in kinds
        assert 'checkpoint_save' in kinds
        assert 'preemption' in kinds
        rels = [row['t_rel_s'] for row in rep['timeline']]
        assert rels == sorted(rels)

    def test_human_render(self, tmp_path):
        d = str(tmp_path)
        self._make_run(d)
        p = self._run(d)
        assert p.returncode == 0, p.stderr
        assert 'run report' in p.stdout
        assert 'step times' in p.stdout
        assert 'resilience timeline' in p.stdout

    def test_flightrec_only_input(self, tmp_path):
        """Post-mortem mode: a flight dump alone (no JSONL — the
        worker died before streaming) still yields a report."""
        r = telemetry.get_recorder()
        r.event('preemption', signum=15)
        r.dump_flight(str(tmp_path / 'flightrec-3.json'))
        p = self._run(str(tmp_path / 'flightrec-3.json'), '--json')
        assert p.returncode == 0, p.stderr
        rep = json.loads(p.stdout)
        assert [row['kind'] for row in rep['timeline']][0] == \
            'preemption'

    def test_no_input_is_usage_error(self, tmp_path):
        p = self._run(str(tmp_path))
        assert p.returncode == 2
