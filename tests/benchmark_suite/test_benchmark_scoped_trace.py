"""benchmark/scoped_trace.py and the three readers built on it
(scope_ms, scope_share, host_span_ms), on a fixture cut from a chip's
trace and on intervals small enough to reckon by hand.

The fixture, fixtures/chat_scoped_cut.xplane.pb, is cut from the traced
run of serve_chat_steady that PR 25 looked at by hand (TPU v5 lite,
seed 2147483077): the host line 'python' with the program's and the
harness's spans of two whole interventions, the first with a prefill,
and of the last 30 ms of the intervention before (its children are
there, itself it is not: as where the profiler starts inside one), with
'bench.traced' redrawn around them and some forty of jax's own host
events left in to be passed over; of the chip's plane the line 'XLA
Ops' with the ops of 100 us and more, instruction names cut to 60
characters, and of each instruction's stats the one that carries the
scope, `tf_op`, whole.  Ops under 100 us are gone, so their time shows
as self time of the `while` around them.
"""
import importlib
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import HERE, REPO
from benchmark import harness, reduce_trace as rt, scoped_trace as sc
from benchmark.readers import scope_ms as SCOPE_MS

FIXTURE = os.path.join(HERE, 'fixtures', 'chat_scoped_cut.xplane.pb')
UNSCOPED_FIXTURE = os.path.join(HERE, 'fixtures', 'train_cut.xplane.pb')
SERVE_CONFIG = {'serve': {'decode_span': 8}}

with open(os.path.join(REPO, 'BENCHMARK.json')) as _f:
    NEW_METRICS = [m['name'] for m in json.load(_f)['per_layer']
                   if m['source'] in ('program_span', 'device_trace')
                   and m['name'].split('.')[0] in (
                       'ce_head_ms_per_step', 'optimizer_ms_per_step',
                       'dispatch_ms_per_step', 'prefill_device_share',
                       'engine_host_ms',
                       'unscoped_device_share')]


@pytest.fixture(scope='module')
def chat():
    return sc.ScopedTrace.from_file(FIXTURE)


def by_hand():
    """One 'train step' of 100 ns drawn by hand: a jitted module whose
    `while` (10..60) holds a forward CE op, a backward CE op and an op
    XLA left without a name; then an optimizer fusion, a Pallas kernel
    outside every scope and a copy.  Host: trainer.step with its three
    children inside the harness's spans."""
    ops = [
        ('%while.1 = while()', 10., 60., 'jit(train_step)/while:'),
        ('%fusion.1 = fusion()', 10., 30.,
         'jit(train_step)/jvp(fused_ce.fwd)/while/body/dot_general:'),
        ('%fusion.2 = fusion()', 30., 45.,
         'jit(train_step)/transpose(jvp(fused_ce.bwd))/while/body/mul:'),
        ('%copy.3 = copy()', 50., 55., ''),
        ('%fusion.4 = fusion()', 60., 70.,
         'jit(train_step)/optimizer_update/sub:'),
        ('%softmax_fwd.5 = custom-call()', 70., 74.,
         'jit(train_step)/softmax_fwd/pallas_call:'),
        ('%fusion.6 = fusion()', 80., 90.,
         'jit(train_step)/jvp(gpt.attn)/gpt.ln/add:'),
    ]
    spans = [
        (rt.TRACED_SPAN, 0., 100.),
        ('bench.train_step', 0., 9.),
        ('trainer.step', 1., 9.),
        ('trainer.prepare', 1., 4.),
        ('trainer.dispatch', 4., 8.),
        ('trainer.note', 8., 8.5),
        ('bench.wait_step', 9., 100.),
    ]
    return sc.ScopedTrace({0: ops}, spans)


def ctx_of(st, **more):
    return {'scoped_trace': st, 'counters': {'traced_steps': 1},
            'config': SERVE_CONFIG, **more}


def read_metric(name, ctx):
    spec = harness.load_json(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name + '.json'))
    return harness.read_layer_metrics(
        [{'name': name, 'unit': '-', **spec}], ctx).get(name)


# -- the fixture ---------------------------------------------------------------
def test_fixture_keeps_the_scope_of_each_op(chat):
    assert sorted(chat.device_ops) == [0]
    ops = chat.device_ops[0]
    assert len(ops) > 1000
    scoped = [op for _, _, _, op in ops if sc.innermost_scope(op)]
    assert len(scoped) > 0.9 * len(ops)
    assert any('paged.attention/paged.gather_dense/gather' in op
               for op in scoped)
    assert any(op.startswith('jit(prefill_fn)/serve.prefill/')
               for op in scoped)
    # only the program's and the harness's spans are kept of the host
    names = {n for n, _, _ in chat.host_spans}
    assert rt.TRACED_SPAN in names and 'serve.step' in names
    assert all(n.startswith(('serve.', 'bench.')) for n in names)


def test_same_clock_and_window_as_reduce_trace(chat):
    plain = rt.Trace.from_file(FIXTURE)
    assert chat.window() == pytest.approx(plain.window())
    ours, theirs = chat.device_ops[0], plain.device_ops[0]
    assert [ev[0] for ev in ours] == [ev[0] for ev in theirs]
    assert [ev[:3] for ev in ours] == theirs    # to the nanosecond
    summary = rt.summary(plain)
    assert chat.busy_ns() / 1e9 == pytest.approx(summary['busy_s'])
    assert (chat.busy_ns() + chat.idle_ns()) / 1e9 \
        == pytest.approx(summary['window_s'])


def test_the_three_tables_add_up(chat):
    busy, idle = chat.busy_ns(), chat.idle_ns()
    by_scope = chat.by_scope()
    assert sum(by_scope.values()) == pytest.approx(busy)
    assert max(by_scope, key=by_scope.get) == 'paged.gather_dense'
    by_span = chat.idle_by_span()
    assert sum(by_span.values()) == pytest.approx(idle)
    assert all(ns > 0 for ns in by_span.values())
    own = chat.span_self()
    lo, hi = chat.window()
    assert sum(ns for ns, _ in own.values()) <= hi - lo
    assert own['serve.step'][1] == 2            # two whole interventions
    assert own['serve.decode_sync'][0] > 0.8 * (hi - lo)


def test_children_cover_their_step_and_hold_the_idle_time(chat):
    least, mean = sc.step_cover(chat)
    assert 0.95 <= least <= mean <= 1.0
    steps = chat.spans('serve.step')
    kids = [n for n, _, _ in chat.children(steps[0])]
    once = [n for i, n in enumerate(kids) if n not in kids[:i]]
    assert once == [
        'serve.deadlines', 'serve.admit', 'serve.prefill_dispatch',
        'serve.first_token_sync', 'serve.reserve', 'serve.plan',
        'serve.decode_dispatch', 'serve.decode_sync', 'serve.absorb',
        'serve.bookkeeping']
    assert 0.8 < sc.idle_inside_children(chat) <= 1.0


def test_tables_print_from_the_command_line(chat):
    out = subprocess.run(
        [sys.executable, '-m', 'benchmark.scoped_trace', FIXTURE],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == sc.tables(chat).replace(
        str(chat.path), FIXTURE)
    for title in ('by innermost program scope', 'host self time by span',
                  'idle time by innermost program span',
                  'children cover of a step span'):
        assert title in out.stdout
    assert 'paged.gather_dense' in out.stdout


# -- by hand -------------------------------------------------------------------
def test_innermost_scope_reads_forward_backward_and_nested_paths():
    assert sc.innermost_scope(
        'jit(decode_fn)/serve.decode/while/body/closed_call/gpt.attn/'
        'paged.attention/paged.gather_dense/gather:') \
        == 'paged.gather_dense'
    assert sc.innermost_scope(
        'jit(train_step)/transpose(jvp(gpt.mlp))/dot_general:') \
        == 'gpt.mlp'
    assert sc.innermost_scope('jit(train_step)/jvp()/reshape:') is None
    assert sc.innermost_scope('') is None
    assert sc.is_pallas('jit(f)/jvp(gpt.attn)/flash_fwd/pallas_call:')


def test_innermost_segments_by_hand():
    spans = [('a', 0., 10.), ('b', 2., 5.), ('c', 3., 4.),
             ('d', 12., 14.)]
    assert sc.innermost_segments(spans) == [
        (0., 2., 'a'), (2., 3., 'b'), (3., 4., 'c'), (4., 5., 'b'),
        (5., 10., 'a'), (12., 14., 'd')]
    assert sc.innermost_segments([]) == []


def test_tables_by_hand():
    st = by_hand()
    assert st.window() == (0., 100.)
    assert st.busy_ns() == pytest.approx(74.)       # 10..74 and 80..90
    assert st.idle_ns() == pytest.approx(26.)
    assert st.by_scope() == pytest.approx({
        'fused_ce.fwd': 20., 'fused_ce.bwd': 15., '(no scope) while': 10.,
        '(no scope) copy': 5., 'optimizer_update': 10.,
        '(no scope) softmax_fwd': 4., 'gpt.ln': 10.})
    assert st.scope_ns(r'fused_ce\.') == (pytest.approx(35.), 2)
    assert st.scope_ns('gpt.attn') == (pytest.approx(10.), 1)  # outer too
    assert st.unscoped_ns() == pytest.approx(15.)   # the kernel is none
    # idle: 0..10 and 74..80 and 90..100
    assert st.idle_by_span() == pytest.approx({
        '(bench.train_step)': 1., 'trainer.prepare': 3.,
        'trainer.dispatch': 4., 'trainer.note': .5, 'trainer.step': .5,
        '(bench.wait_step)': 17.})
    assert sc.idle_inside_children(st) == pytest.approx(7.5 / 26.)
    assert sc.step_cover(st) == pytest.approx((7.5 / 8., 7.5 / 8.))
    assert st.span_self()['trainer.step'] == [pytest.approx(.5), 1]
    # a span that began before the window is no whole span, but counts
    # as begun only inside it
    st.host_spans.append(('trainer.step', -5., 0.5))
    assert len(st.spans('trainer.step')) == 1
    assert len(st.begun('trainer.step')) == 1


# -- the readers, through each metric's own file ---------------------------------
def test_the_new_metrics_are_the_nine():
    # eleven until PR 30: no op on the chip carries paged.gather_dense
    # since PR 26, so gather_ms_per_token_step.backlog|chat went
    assert len(NEW_METRICS) == 9
    assert not any('gather' in name for name in NEW_METRICS)


def expected(name, chat):
    """What a metric should read on the trace it is handed here: the
    chat fixture for the serving cells' metrics, the hand-drawn step
    for the training cell's."""
    st = chat if name.split('.')[-1] in ('chat', 'backlog') else by_hand()
    steps = chat.spans('serve.step')
    return st, {
        'ce_head_ms_per_step': 35e-6,
        'optimizer_ms_per_step': 10e-6,
        'dispatch_ms_per_step': 8e-6,
        'prefill_device_share': 100 * chat.scope_ns(
            r'serve\.prefill')[0] / chat.busy_ns(),
        'engine_host_ms': sum(
            e - s - sum(ce - cs for n, cs, ce in chat.children((n0, s, e))
                        if n.endswith('_sync'))
            for n0, s, e in steps) / len(steps) / 1e6,
        'unscoped_device_share':
            100 * st.unscoped_ns() / st.busy_ns(),
    }[name.split('.')[0]]


@pytest.mark.parametrize('name', NEW_METRICS)
def test_metric_reads_its_scope_or_span(name, chat):
    st, want = expected(name, chat)
    got = read_metric(name, ctx_of(st))
    assert got is not None
    assert got['value'] == pytest.approx(want)
    assert got['value'] > 0
    if name.endswith(('share.chat', 'share.backlog', 'share.train')):
        assert got['value'] < 100


def test_serving_readings_are_the_size_the_chip_run_showed(chat):
    """The numbers of the fixture's own run, so a reader that drifts
    from what PERF.md reports is seen: 12 to 14 ms of gather a token
    step, a few ms of host time an intervention."""
    ctx = ctx_of(chat)
    # the fixture IS a gather-path trace (PR 25's); no metric reads that
    # scope on the chip since PR 26, the reader still does
    assert 12 < SCOPE_MS.read(
        {'scope': r'paged\.gather_dense',
         'per_span': 'serve.decode_dispatch',
         'times_config': ['serve', 'decode_span']}, ctx) < 14
    assert 2 < read_metric('engine_host_ms.chat', ctx)['value'] < 12
    assert 0 < read_metric('prefill_device_share.chat', ctx)['value'] < 5
    # the spans the reader subtracts are the waits for the device
    whole = importlib.import_module('benchmark.readers.host_span_ms').read(
        {'span': 'serve.step'}, ctx)
    assert whole > 250


@pytest.mark.parametrize('times_config', [['serve', 'decode_span'], None])
@pytest.mark.parametrize('scope', [r'paged\.gather_dense',
                                   r'paged\.attention'])
def test_scope_ms_divides_by_spans_begun_times_the_configured_number(
        chat, scope, times_config):
    """The reader itself, with parameters given here and not through a
    metric's file: device self time under `scope` over the spans begun
    in the window (two in the fixture), times the number at
    `times_config` where one is given.  The outer scope holds the
    inner one's ops, so it never reads less."""
    params = {'scope': scope, 'per_span': 'serve.decode_dispatch'}
    if times_config:
        params['times_config'] = times_config
    begun = len(chat.begun('serve.decode_dispatch'))
    assert begun == 2
    units = begun * (SERVE_CONFIG['serve']['decode_span']
                     if times_config else 1)
    got = SCOPE_MS.read(params, ctx_of(chat))
    assert got == pytest.approx(chat.scope_ns(scope)[0] / 1e6 / units)
    inner = SCOPE_MS.read(dict(params, scope=r'paged\.gather_dense'),
                          ctx_of(chat))
    assert got >= inner > 0
    # a scope no op carries: nothing to read, never 0
    assert SCOPE_MS.read(dict(params, scope=r'paged\.no_such_scope'),
                         ctx_of(chat)) is None


@pytest.mark.parametrize('name', NEW_METRICS)
def test_metric_is_left_out_where_the_program_names_nothing(name):
    """The parent of PR 25 has no scope and no span: on its trace (PR
    24's fixture, whose ops carry no op_name) every new reader finds
    nothing, returns None and raises nothing, and the harness leaves
    the metric out of the line."""
    st = sc.ScopedTrace.from_file(UNSCOPED_FIXTURE)
    assert st.busy_ns() > 0 and not st.names_scopes()
    assert read_metric(name, ctx_of(st)) is None
    # and where there is no chip trace at all (a CPU run)
    assert read_metric(name, {'trace': None, 'counters': {},
                              'config': SERVE_CONFIG}) is None


def test_for_ctx_finds_the_newest_trace_and_prints_once(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(harness, 'OUT_DIR', str(tmp_path))
    assert sc.newest_xplane() is None
    assert sc.for_ctx({'trace': object()}) is None
    for i, (cell, src) in enumerate((('older', UNSCOPED_FIXTURE),
                                     ('newer', FIXTURE))):
        d = tmp_path / 'trace' / cell / 'plugins' / 'profile' / 'x'
        d.mkdir(parents=True)
        (d / 'host.xplane.pb').write_bytes(open(src, 'rb').read())
        os.utime(d / 'host.xplane.pb', (1000 + i, 1000 + i))
    assert sc.newest_xplane().split(os.sep)[-5] == 'newer'
    ctx = {'trace': object(), 'counters': {}, 'config': SERVE_CONFIG}
    capsys.readouterr()
    st = sc.for_ctx(ctx)
    assert st is sc.for_ctx(ctx) and st.names_scopes()
    printed = capsys.readouterr()
    assert printed.out == ''
    assert printed.err.count('by innermost program scope') == 1
