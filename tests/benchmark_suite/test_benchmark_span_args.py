"""The readers of the program's span arguments (PR 37): `span_args`
and `paged_span_roofline`, through the nine metrics' own files, on a
trace drawn by hand (with a dispatch in flight as the window opens and
one running on after it closes), on a trace the profiler writes here
on the CPU,
and on traces whose spans carry nothing (PR 25's chat fixture, PR 24's
unscoped one): there, and where the run has no chip trace, every
metric is left out."""
import json
import os

import pytest

from bench_helpers import HERE, REPO
from benchmark import harness, reduce_trace as rt, scoped_trace as sc
from benchmark.readers import paged_span_roofline, span_args

FIXTURES = [os.path.join(HERE, 'fixtures', name) for name in
            ('chat_scoped_cut.xplane.pb', 'train_cut.xplane.pb')]
GPT_SERVE = harness.load_json(os.path.join(
    REPO, 'benchmark', 'configs', 'cerebras_gpt_1p3b_serve.json'))

with open(os.path.join(REPO, 'BENCHMARK.json')) as _f:
    METRICS = [m['name'] for m in json.load(_f)['per_layer']
               if m['name'].split('.')[0] in (
                   'paged_decode_roofline', 'decode_ahead_share',
                   'prefill_us_per_token')
               and m['source'] == 'program_span']

MS = 1e6                # the trace's clock is in ns
DECODE_OP = ('jit(decode_fn)/serve.decode/while/body/gpt.attn/'
             'paged.attention/paged_decode/pallas_call:')
PREFILL_OP = 'jit(prefill_fn)/serve.prefill/gpt.mlp/dot_general:'
DECODE_MLP = 'jit(decode_fn)/serve.decode/while/body/gpt.mlp/dot_general:'
DECODE = ('serve.decode_dispatch', 'serve.absorb', 'jit_decode_fn')
PREFILL = ('serve.prefill_dispatch', 'serve.first_token_sync',
           'jit_prefill_fn')


def by_hand():
    """A window of 100 ms.  Decode dispatches: one sent before it and
    still running as it opens, two sent and run in it (the first with
    a module op outside `paged.attention`), one sent in it whose run
    outlasts it, one sent after it.  Prefills: one sent before it and
    run in it, one sent and run in it, one sent in it and run after
    it.  Of the dispatches run whole in the window and sent in it, 26
    ms under `paged.attention` and 10 ms under `serve.prefill`."""
    spans = [
        ('serve.decode_dispatch', -5 * MS, -4 * MS,
         {'dispatch': 1, 'ahead': 0, 'kv_blocks': 9999}),
        ('serve.prefill_dispatch', -3 * MS, -2 * MS,
         {'dispatch': 1, 'rows': 1, 'tokens': 300, 'padded': 512}),
        ('serve.decode_dispatch', 10 * MS, 11 * MS,
         {'dispatch': 2, 'ahead': 1, 'kv_blocks': 1000}),
        ('serve.absorb', 26 * MS, 27 * MS, {'dispatch': 1, 'tokens': 8}),
        ('serve.first_token_sync', 27 * MS, 31 * MS, {'dispatch': 1}),
        ('serve.decode_dispatch', 40 * MS, 41 * MS,
         {'dispatch': 3, 'ahead': 1, 'kv_blocks': 1200}),
        ('serve.prefill_dispatch', 45 * MS, 46 * MS,
         {'dispatch': 2, 'rows': 1, 'tokens': 100, 'padded': 128}),
        ('serve.absorb', 51 * MS, 52 * MS, {'dispatch': 2, 'tokens': 8}),
        ('serve.first_token_sync', 52 * MS, 61 * MS, {'dispatch': 2}),
        ('serve.decode_dispatch', 70 * MS, 71 * MS,
         {'dispatch': 4, 'ahead': 0, 'kv_blocks': 1400}),
        ('serve.absorb', 81 * MS, 82 * MS, {'dispatch': 3, 'tokens': 8}),
        ('serve.prefill_dispatch', 97 * MS, 98 * MS,
         {'dispatch': 3, 'rows': 1, 'tokens': 5000, 'padded': 8192}),
        ('serve.decode_dispatch', 110 * MS, 111 * MS,
         {'dispatch': 5, 'ahead': 1, 'kv_blocks': 9999}),
        ('serve.absorb', 116 * MS, 117 * MS, {'dispatch': 4, 'tokens': 8}),
        ('serve.first_token_sync', 117 * MS, 131 * MS, {'dispatch': 3}),
    ]
    runs = {'jit_decode_fn': [(-3 * MS, 25 * MS), (30 * MS, 50 * MS),
                              (60 * MS, 80 * MS), (90 * MS, 115 * MS)],
            'jit_prefill_fn': [(25 * MS, 30 * MS), (50 * MS, 60 * MS),
                               (115 * MS, 130 * MS)]}
    ops = [('%paged_decode.1 = custom-call()', -3 * MS, 5 * MS, DECODE_OP),
           ('%paged_decode.2 = custom-call()', 15 * MS, 25 * MS, DECODE_OP),
           ('%fusion.3 = fusion()', 25 * MS, 30 * MS, PREFILL_OP),
           ('%paged_decode.4 = custom-call()', 35 * MS, 45 * MS, DECODE_OP),
           ('%fusion.5 = fusion()', 46 * MS, 49 * MS, DECODE_MLP),
           ('%fusion.6 = fusion()', 50 * MS, 60 * MS, PREFILL_OP),
           ('%paged_decode.7 = custom-call()', 62 * MS, 78 * MS, DECODE_OP),
           ('%paged_decode.8 = custom-call()', 92 * MS, 112 * MS,
            DECODE_OP),
           ('%fusion.9 = fusion()', 115 * MS, 130 * MS, PREFILL_OP)]
    host = [(rt.TRACED_SPAN, 0.0, 100 * MS)] + [s[:3] for s in spans]
    st = sc.ScopedTrace({0: ops}, sorted(host, key=lambda t: t[1]),
                        path='by_hand.xplane.pb')
    return {'scoped_trace': st, 'span_args': {st.path: (spans, runs)},
            'config': GPT_SERVE, 'device_kind': 'TPU v5 lite'}


def read_metric(name, ctx):
    spec = harness.load_json(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name + '.json'))
    return harness.read_layer_metrics(
        [{'name': name, 'unit': '-', **spec}], ctx).get(name)


def test_the_metrics_are_the_nine():
    assert len(METRICS) == 9


def expected_by_hand(name):
    # the issue's arithmetic: 262,144 B a block and layer in float32,
    # 24 layers, 819 GB/s; of the dispatches sent and run in the window
    need_s = (1000 + 1200) * 262144 * 24 / 819e9
    return {'paged_decode_roofline': 100 * need_s / 26e-3,
            'decode_ahead_share': 100 * 2 / 3,
            'prefill_us_per_token': 10e6 / 100 / 1e3,
            }[name.split('.')[0]]


@pytest.mark.parametrize('name', METRICS)
def test_each_metric_reads_the_spans_begun_in_the_window(name):
    got = read_metric(name, by_hand())
    assert got is not None
    assert got['value'] == pytest.approx(expected_by_hand(name))


def test_the_roofline_counts_a_block_as_the_pool_holds_it():
    assert paged_span_roofline.block_bytes(GPT_SERVE) == 262144 * 24
    half = dict(GPT_SERVE, kv_pool=dict(GPT_SERVE['kv_pool'],
                                        dtype='bfloat16'))
    assert paged_span_roofline.block_bytes(half) == 262144 * 12


def test_a_span_begun_outside_the_window_is_not_counted():
    ctx = by_hand()
    begun = span_args.spans(ctx, 'serve.decode_dispatch')
    assert [a['dispatch'] for a, _ in begun] == [2, 3, 4]
    assert span_args.read({'span': 'serve.decode_dispatch',
                           'sum': 'kv_blocks'}, ctx) == 3600
    assert span_args.read({'span': 'serve.decode_dispatch',
                           'num': 'kv_blocks', 'den': 'count'},
                          ctx) == 1200
    # an argument no span carries: nothing to read, never 0
    assert span_args.read({'span': 'serve.decode_dispatch',
                           'sum': 'no_such'}, ctx) is None
    assert span_args.read({'span': 'serve.no_such', 'num': 'count',
                           'den': 'count'}, ctx) is None


def test_work_and_device_time_are_of_the_same_dispatches():
    """The dispatch in flight as the window opens (its run began before
    it) and the one whose run outlasts it count on neither side; the
    prefill sent before the window counts on neither though it ran in
    it, since its arguments lie outside."""
    ctx = by_hand()
    held = span_args.spans(ctx, *DECODE)
    assert [(a['dispatch'], run) for a, run in held] == [
        (2, (30 * MS, 50 * MS)), (3, (60 * MS, 80 * MS))]
    assert span_args.scope_ns(ctx, r'paged\.attention',
                              [run for _, run in held]) == (26 * MS, 2)
    assert [a['dispatch'] for a, _ in span_args.spans(ctx, *PREFILL)] \
        == [2]
    assert span_args.read({'span': 'serve.prefill_dispatch',
                           'sum': 'tokens'}, ctx) == 5100
    assert span_args.read(dict(zip(('span', 'read_by', 'module'),
                                   PREFILL), sum='tokens'), ctx) == 100
    # a reading that carries no number, or no run of the module: none
    assert span_args.read({'span': 'serve.decode_dispatch',
                           'read_by': 'serve.no_such',
                           'module': 'jit_decode_fn',
                           'sum': 'kv_blocks'}, ctx) is None
    assert span_args.read({'span': 'serve.decode_dispatch',
                           'read_by': 'serve.absorb', 'module': 'no_such',
                           'sum': 'kv_blocks'}, ctx) is None


def test_a_run_shorter_than_the_hosts_wait_does_not_shift_the_match():
    """Two chunks sent back to back: the second ran whole before the
    host's wait for the first ended.  The least offset over the
    readings is still the true one."""
    spans = [('serve.prefill_dispatch', 1 * MS, 2 * MS,
              {'dispatch': 7, 'tokens': 10}),
             ('serve.prefill_dispatch', 2 * MS, 3 * MS,
              {'dispatch': 8, 'tokens': 20}),
             ('serve.first_token_sync', 3 * MS, 22 * MS, {'dispatch': 7}),
             ('serve.first_token_sync', 22 * MS, 23 * MS, {'dispatch': 8})]
    runs = {'jit_prefill_fn': [(10 * MS, 20 * MS), (20 * MS, 21 * MS)]}
    host = [(rt.TRACED_SPAN, 0.0, 50 * MS)] + [s[:3] for s in spans]
    st = sc.ScopedTrace({0: []}, host, path='short.xplane.pb')
    ctx = {'scoped_trace': st, 'span_args': {st.path: (spans, runs)}}
    assert [(a['dispatch'], run) for a, run in
            span_args.spans(ctx, *PREFILL)] == [
        (7, (10 * MS, 20 * MS)), (8, (20 * MS, 21 * MS))]


def test_a_trace_the_profiler_wrote_is_read_through_its_file(tmp_path):
    """The spans' arguments as the profiler writes them (jax's own
    annotations, here on the CPU), read back from the file once."""
    import jax
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation('serve.decode_dispatch', ahead=0, kv_blocks=7):
            pass
        with TraceAnnotation(rt.TRACED_SPAN):
            for ahead in (1, 1, 0, 1):
                with TraceAnnotation('serve.decode_dispatch', ahead=ahead,
                                     kv_blocks=5):
                    pass
            with TraceAnnotation('serve.prefill_dispatch', dispatch=1,
                                 tokens=9):
                pass
            with TraceAnnotation('serve.first_token_sync', dispatch=1):
                pass
    finally:
        jax.profiler.stop_trace()
    path = rt.find_xplane(str(tmp_path))
    ctx = {'scoped_trace': sc.ScopedTrace.from_file(path),
           'config': GPT_SERVE, 'device_kind': 'TPU v5 lite'}
    assert read_metric('decode_ahead_share.backlog', ctx)['value'] \
        == pytest.approx(75.0)
    assert span_args.read({'span': 'serve.decode_dispatch',
                           'sum': 'kv_blocks'}, ctx) == 20
    assert span_args.read({'span': 'serve.prefill_dispatch',
                           'sum': 'tokens'}, ctx) == 9
    assert list(ctx['span_args']) == [path]
    # no chip on the CPU: no module run and no device time to divide by
    assert span_args.spans(ctx, *PREFILL) == []
    assert read_metric('paged_decode_roofline.backlog', ctx) is None
    assert read_metric('prefill_us_per_token.chat', ctx) is None


@pytest.mark.parametrize('fixture', FIXTURES)
@pytest.mark.parametrize('name', METRICS)
def test_left_out_where_the_spans_carry_nothing(name, fixture):
    """The parent's trace: spans without arguments (PR 25's chat
    fixture) or no spans at all (PR 24's): nothing read, nothing
    raised; and a run without a chip trace (the CPU)."""
    ctx = {'scoped_trace': sc.ScopedTrace.from_file(fixture),
           'config': GPT_SERVE, 'device_kind': 'TPU v5 lite'}
    assert read_metric(name, ctx) is None
    assert read_metric(name, {'trace': None, 'counters': {},
                              'config': GPT_SERVE}) is None
