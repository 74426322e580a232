"""Self-profiling runtime (telemetry.profile + profiler.trace).

Canned-trace parsing, the census join (opcode + replica-group/byte
signature by instruction name), the sampled ProfileSchedule, the
stdlib TensorBoard exporter, and ONE real end-to-end capture on the
dp=8 CPU mesh proving collective_observed events land and calibrate
into a cost-model table — the predicted-vs-observed loop closing with
zero hand-written fixtures.

NOTE this file must sort alphabetically before test_host_embedding.py
(the seed's tier-1 run aborts there), and stays lean: exactly two jit
compiles and two jax.profiler windows — the suite already brushes its
870s budget.
"""
import gzip
import importlib.util
import json
import os
import struct

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import nn, telemetry
from paddle_tpu.profiler import trace as ptrace
from paddle_tpu.telemetry import profile as tprofile
from paddle_tpu.analysis import costmodel, hlo as ahlo

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, 'tools', f'{name}.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def fresh_recorder():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _x(name, dur, pid=1, ts=0):
    return {'ph': 'X', 'name': name, 'dur': dur, 'pid': pid, 'ts': ts}


# ------------------------------------------------ trace parsing ------
class TestTraceParse:
    def test_op_aggregation_filters_infra(self):
        doc = {'traceEvents': [
            _x('all-reduce', 100), _x('all-reduce', 140),
            _x('dot.1', 50), _x('broadcast_multiply_fusion', 10),
            _x('TfrtCpuExecutable::ExecuteHelper', 999),
            _x('ThunkExecutor::Execute (wait for completion)', 999),
            _x('PjitFunction(step)', 999), _x('ParseArguments', 9),
            _x('$profiler.py:91 start_trace', 999),
            {'ph': 'M', 'name': 'process_name', 'pid': 1,
             'args': {'name': '/host:CPU'}},
        ]}
        prof = ptrace.parse_trace(doc)
        assert set(prof.ops) == {'all-reduce', 'dot.1',
                                 'broadcast_multiply_fusion'}
        ar = prof.ops['all-reduce']
        assert ar['count'] == 2
        assert ar['total_us'] == pytest.approx(240.0)
        assert ar['avg_us'] == pytest.approx(120.0)
        assert prof.device_total_us == pytest.approx(300.0)
        assert prof.collective_total_us == pytest.approx(240.0)
        assert set(prof.collectives()) == {'all-reduce'}

    def test_device_pid_restriction(self):
        doc = {'traceEvents': [
            {'ph': 'M', 'name': 'process_name', 'pid': 7,
             'args': {'name': '/device:TPU:0'}},
            {'ph': 'M', 'name': 'process_name', 'pid': 8,
             'args': {'name': 'python'}},
            _x('fusion.3', 30, pid=7),
            _x('fusion.3', 999, pid=8),     # host-side shadow
        ]}
        prof = ptrace.parse_trace(doc)
        assert prof.ops['fusion.3']['count'] == 1
        assert prof.ops['fusion.3']['total_us'] == pytest.approx(30.0)
        assert prof.device_pids == 1

    def test_collective_base(self):
        assert ptrace.collective_base('all-reduce') == 'all-reduce'
        assert ptrace.collective_base('all-reduce-start.3') == \
            'all-reduce'
        assert ptrace.collective_base('reduce-scatter.12') == \
            'reduce-scatter'
        assert ptrace.collective_base('dot.1') is None
        assert ptrace.collective_base('reduce.1') is None

    def test_gz_file_roundtrip(self, tmp_path):
        d = tmp_path / 'plugins' / 'profile' / 'run1'
        d.mkdir(parents=True)
        p = str(d / 'host.trace.json.gz')
        with gzip.open(p, 'wt') as f:
            json.dump({'traceEvents': [_x('all-gather', 12)]}, f)
        found = ptrace.find_traces(str(tmp_path))
        assert found == [p]
        prof = ptrace.parse_trace(p)
        assert prof.ops['all-gather']['total_us'] == pytest.approx(12.0)
        assert prof.source == p


# ---------------------------------------------- census matching ------
_HLO = """\
HloModule jit_step, num_partitions=8

ENTRY %main (p0: f32[128,16]) -> f32[128,16] {
  %p0 = f32[128,16]{1,0} parameter(0)
  %all-reduce = f32[128,16]{1,0} all-reduce(%p0), replica_groups=[1,8]<=[8], to_apply=%add, source_file="m.py" source_line=3
  ROOT %copy = f32[128,16]{1,0} copy(%all-reduce)
}
"""


class TestCensusMatch:
    def test_collective_instrs_signature(self):
        mod = ahlo.parse_module(_HLO)
        idx = ahlo.collective_instrs(mod, mesh_shape={'dp': 8})
        assert set(idx) == {'all-reduce'}
        row = idx['all-reduce']
        buf = 128 * 16 * 4
        assert row['op'] == 'all-reduce'
        assert row['bytes'] == buf
        assert row['group_size'] == 8
        # ring all-reduce: 2*(n-1)/n of the buffer, 2*(n-1) phases
        assert row['wire_bytes'] == 2 * 7 * buf // 8
        assert row['phases'] == 14
        assert row['est_us'] > 0
        # aggregating by base opcode reproduces the census row
        census = ahlo.collective_census(mod, mesh_shape={'dp': 8})
        assert census['all-reduce']['wire_bytes'] == row['wire_bytes']

    def test_match_collectives_join(self):
        mod = ahlo.parse_module(_HLO)
        idx = ahlo.collective_instrs(mod, mesh_shape={'dp': 8})
        prof = ptrace.parse_trace({'traceEvents': [
            _x('all-reduce', 100) for _ in range(16)]})  # 8 dev x 2 st
        rows = ptrace.match_collectives(prof, idx, num_partitions=8)
        assert len(rows) == 1
        r = rows[0]
        assert r['op'] == 'all-reduce' and r['instr'] == 'all-reduce'
        assert r['us'] == pytest.approx(100.0)
        assert r['calls'] == 2
        assert r['wire_bytes'] == idx['all-reduce']['wire_bytes']
        assert r['phases'] == 14
        assert r['predicted_us'] == idx['all-reduce']['est_us']

    def test_match_async_start_alias(self):
        mod = ahlo.parse_module(_HLO)
        idx = ahlo.collective_instrs(mod, mesh_shape={'dp': 8})
        # backend timed the async '-start' half of the pair
        prof = ptrace.parse_trace({'traceEvents': [
            _x('all-reduce-start', 55) for _ in range(8)]})
        rows = ptrace.match_collectives(prof, idx, num_partitions=8)
        assert len(rows) == 1
        assert rows[0]['us'] == pytest.approx(55.0)

    def test_match_async_alias_keeps_numeric_suffix(self):
        """The '-start' toggle goes INSIDE the numeric suffix:
        census 'all-reduce-start.1' joins trace 'all-reduce.1' (and
        vice versa) — XLA suffixes every collective past the first."""
        info = {'op': 'all-reduce', 'bytes': 64, 'wire_bytes': 112,
                'phases': 14, 'est_us': 1.0, 'group_size': 8,
                'axes': (('dp', 8),)}
        prof = ptrace.parse_trace({'traceEvents': [
            _x('all-reduce.1', 40) for _ in range(8)]})
        rows = ptrace.match_collectives(
            prof, {'all-reduce-start.1': info}, num_partitions=8)
        assert len(rows) == 1 and rows[0]['us'] == pytest.approx(40.0)
        prof = ptrace.parse_trace({'traceEvents': [
            _x('all-reduce-start.2', 41) for _ in range(8)]})
        rows = ptrace.match_collectives(
            prof, {'all-reduce.2': info}, num_partitions=8)
        assert len(rows) == 1 and rows[0]['us'] == pytest.approx(41.0)

    def test_unmatched_census_instr_skipped(self):
        mod = ahlo.parse_module(_HLO)
        idx = ahlo.collective_instrs(mod, mesh_shape={'dp': 8})
        prof = ptrace.parse_trace({'traceEvents': [_x('dot', 10)]})
        assert ptrace.match_collectives(prof, idx) == []


# ------------------------------------------------- schedule ----------
class TestProfileSchedule:
    def test_parse_forms(self):
        assert tprofile.ProfileSchedule.parse(None) is None
        assert tprofile.ProfileSchedule.parse(False) is None
        assert tprofile.ProfileSchedule.parse('off') is None
        assert tprofile.ProfileSchedule.parse('0') is None
        s = tprofile.ProfileSchedule.parse(True)
        assert (s.every, s.steps) == (200, 2)
        s = tprofile.ProfileSchedule.parse(
            'every=4,steps=2,start=3,limit=2,dir=/tmp/p')
        assert (s.every, s.steps, s.start, s.limit, s.dir) == \
            (4, 2, 3, 2, '/tmp/p')
        s = tprofile.ProfileSchedule.parse({'every': 7, 'steps': 1})
        assert (s.every, s.steps) == (7, 1)
        s2 = tprofile.ProfileSchedule.parse(s)
        assert s2 is s

    def test_parse_bad_specs_raise(self):
        with pytest.raises(ValueError):
            tprofile.ProfileSchedule.parse('every')
        with pytest.raises(ValueError):
            tprofile.ProfileSchedule.parse('bogus=3')

    def test_starts_at_and_limit(self):
        s = tprofile.ProfileSchedule(every=10, steps=2, start=5,
                                     limit=2)
        assert s.starts_at(5)
        assert not s.starts_at(6)
        assert s.starts_at(15, windows_done=1)
        assert not s.starts_at(25, windows_done=2)   # limit reached
        assert not s.starts_at(4)
        # windows never include step 0 (compile)
        assert tprofile.ProfileSchedule(start=0).start == 1

    def test_env_resolution(self, monkeypatch):
        monkeypatch.setenv(tprofile.ENV_VAR, 'every=9,steps=1')
        s = tprofile.resolve_schedule(None)
        assert s is not None and s.every == 9
        # explicit False beats the env
        assert tprofile.resolve_schedule(False) is None
        monkeypatch.setenv(tprofile.ENV_VAR, 'off')
        assert tprofile.resolve_schedule(None) is None

    def test_hard_off_disables(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_TELEMETRY', '0')
        assert telemetry.step_profiler(True) is None

    def test_off_by_default(self):
        os.environ.pop(tprofile.ENV_VAR, None)
        assert telemetry.step_profiler(None) is None


# --------------------------------------- TensorBoard event files -----
class TestTensorBoardWriter:
    def test_crc32c_known_value(self):
        from paddle_tpu.telemetry.exporters import _crc32c
        assert _crc32c(b'123456789') == 0xE3069283   # CRC-32C check

    def _records(self, path):
        """Decode the TFRecord framing, verifying both CRCs."""
        from paddle_tpu.telemetry.exporters import _masked_crc
        out = []
        with open(path, 'rb') as f:
            while True:
                header = f.read(8)
                if not header:
                    return out
                (crc_h,) = struct.unpack('<I', f.read(4))
                assert _masked_crc(header) == crc_h
                (n,) = struct.unpack('<Q', header)
                data = f.read(n)
                (crc_d,) = struct.unpack('<I', f.read(4))
                assert _masked_crc(data) == crc_d
                out.append(data)

    def test_event_file_framing_and_scalars(self, tmp_path):
        from paddle_tpu.telemetry import TensorBoardWriter
        w = TensorBoardWriter(str(tmp_path), rank=0)
        w.add_scalar('train/loss', 1.5, step=3)
        w.write({'kind': 'steps', 'tag': 'train', 'n': 2,
                 'step': [4, 5], 'step_time_ms': [1.0, None],
                 'loss': [0.5, 0.25], 'ts': 123.0})
        w.close()
        recs = self._records(w.path)
        assert b'brain.Event:2' in recs[0]
        assert any(b'train/loss' in r for r in recs[1:])
        # step 5's loss rode along; the None step_time was dropped
        assert any(b'train/step_time_ms' in r for r in recs[1:])
        body = [r for r in recs[1:] if b'train/loss' in r][0]
        assert struct.pack('<f', 1.5) in body
        # closed writer drops writes instead of reopening
        w.add_scalar('x', 1.0, 1)
        assert len(self._records(w.path)) == len(recs)

    def test_enable_tensorboard_tees_with_jsonl(self, tmp_path):
        telemetry.enable(str(tmp_path), flush_interval=2,
                         tensorboard=True)
        acc = telemetry.step_accumulator('t')
        acc.observe(step=0, step_time_s=0.001, loss=1.0)
        acc.observe(step=1, step_time_s=0.001, loss=2.0)  # flush
        telemetry.disable()
        tb = [f for f in os.listdir(str(tmp_path))
              if f.startswith('events.out.tfevents.')]
        assert tb, os.listdir(str(tmp_path))
        assert (tmp_path / 'telemetry-r0.jsonl').exists()
        assert any(b't/loss' in r
                   for r in self._records(str(tmp_path / tb[0]))[1:])


# ------------------------------ end-to-end capture + calibration -----
class TestCaptureEndToEnd:
    def _trainer(self, mesh, profile):
        from paddle_tpu.parallel import ParallelTrainer
        paddle.seed(0)
        net = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        mse = nn.MSELoss()
        return ParallelTrainer(net, opt, lambda o, t: mse(o, t),
                               mesh=mesh, profile=profile)

    def test_trainer_window_to_calibration_roundtrip(self, tmp_path):
        """The acceptance loop in-process: dp=8 trainer → sampled
        window → census-matched collective_observed (no fixtures) →
        run_report us_ratio → calibrate_costmodel fit → calibrated
        torus_cost."""
        from paddle_tpu.distributed import env as dist_env
        d = str(tmp_path)
        telemetry.enable(d)
        prev = dist_env.get_mesh()
        mesh = dist_env.build_mesh({'dp': 8})
        dist_env.set_mesh(mesh)
        try:
            tr = self._trainer(mesh, profile={
                'every': 100, 'steps': 2, 'start': 2, 'dir': d})
            rs = np.random.RandomState(0)
            x = rs.randn(16, 8).astype('float32')
            y = rs.randn(16, 4).astype('float32')
            for _ in range(5):
                loss = tr.step(x, y)
            jax.block_until_ready(loss)
        finally:
            dist_env.set_mesh(prev)
        caps = telemetry.events('profile_capture')
        assert len(caps) == 1
        cap = caps[0]
        assert not cap.get('error'), cap
        assert cap['step_lo'] == 2 and cap['step_hi'] == 3
        assert cap['device_us_per_step'] > 0
        assert cap['collective_us_per_step'] > 0
        obs = telemetry.events('collective_observed')
        assert obs, 'no collective_observed events landed'
        for e in obs:
            assert e['op'] == 'all-reduce'
            assert e['wire_bytes'] > 0
            assert e['phases'] > 0
            assert e['us'] >= 0
            assert e['instr']
        # the window left a parseable artifact on disk
        assert ptrace.find_traces(d)
        telemetry.disable()

        # run_report joins observed against the census prediction
        rr = _load_tool('run_report')
        jsonls, flights = rr.discover([d])
        events, sources, skew = rr.load_events(jsonls, flights)
        report = rr.analyze(events, sources, skew)
        row = report['collectives_cmp']['all-reduce']
        assert row['observed_us'] and row['observed_us'] > 0
        assert row['observed_wire_bytes'] > 0
        assert row['predicted_est_us'] > 0
        assert row['us_ratio'] and row['us_ratio'] > 0
        assert report['profile']['windows'] == 1
        assert report['profile']['collective_observed'] == len(obs)

        # calibration fit from the profiled run, consumed by the model
        cc = _load_tool('calibrate_costmodel')
        cal_path = os.path.join(d, 'cal.json')
        assert cc.main([d, '-o', cal_path]) == 0
        cal = costmodel.load_calibration(cal_path)
        fit = cal.per_op['all-reduce']
        assert fit['samples'] == len(obs)
        assert fit['beta_us_per_byte'] >= 0
        c = costmodel.torus_cost('all-reduce', 1 << 16, (8,),
                                 calibration=cal)
        assert c['est_us'] == pytest.approx(
            fit['alpha_us'] * c['phases']
            + fit['beta_us_per_byte'] * c['wire_bytes'], rel=1e-3)

    def test_attached_profiler_outside_window_is_sync_free(
            self, tmp_path):
        """A profiler attached with no window in range costs a step
        nothing the host can see: no device->host transfer."""
        from paddle_tpu.distributed import env as dist_env
        prev = dist_env.get_mesh()
        mesh = dist_env.build_mesh({'dp': 8})
        dist_env.set_mesh(mesh)
        try:
            tr = self._trainer(mesh, profile={
                'every': 1000, 'steps': 1, 'start': 900,
                'dir': str(tmp_path)})
            rs = np.random.RandomState(0)
            x = rs.randn(16, 8).astype('float32')
            y = rs.randn(16, 4).astype('float32')
            tr.step(x, y)       # compile + census outside the guard
            assert tr._profiler is not None
            with jax.transfer_guard_device_to_host('disallow'):
                for _ in range(4):
                    tr.step(x, y)
        finally:
            dist_env.set_mesh(prev)
        assert telemetry.events('profile_capture') == []

    def test_profile_off_is_inert(self):
        from paddle_tpu.distributed import env as dist_env
        os.environ.pop(tprofile.ENV_VAR, None)
        dist_env.set_mesh(None)
        tr = self._trainer(None, profile=False)
        rs = np.random.RandomState(0)
        x = rs.randn(8, 8).astype('float32')
        y = rs.randn(8, 4).astype('float32')
        tr.step(x, y)
        tr.step(x, y)
        assert tr._profiler is None
        assert telemetry.events('profile_capture') == []

    def test_fit_profile_window(self, tmp_path):
        """hapi fit(profile=) closes a window with the breakdown
        (no census join on the meshless path — documented)."""
        paddle.seed(0)
        net = nn.Linear(4, 2)
        model = paddle.hapi.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        model.prepare(optimizer=opt, loss=nn.MSELoss())
        rs = np.random.RandomState(0)
        data = [[rs.randn(8, 4).astype('float32'),
                 rs.randn(8, 2).astype('float32')]] * 5
        model.fit(data, epochs=1, verbose=0,
                  save_dir=str(tmp_path),
                  profile={'every': 100, 'steps': 1, 'start': 2})
        caps = telemetry.events('profile_capture')
        assert len(caps) == 1
        assert not caps[0].get('error'), caps[0]
        assert caps[0]['name'] == 'fit'
        assert caps[0]['device_us_per_step'] > 0
        # artifacts landed next to the flight-dump home (save_dir)
        assert ptrace.find_traces(str(tmp_path))
