"""The reduction from a profiler trace to busy, idle, kernel time and
gaps, on a fixture cut from a chip's trace (one step of the 1.3B-width
trainer, ops of 2 us and more, names cut to 90 characters) and on
intervals small enough to reckon by hand."""
import os

import pytest

from bench_helpers import HERE
from benchmark import flops, reduce_trace as rt

FIXTURE = os.path.join(HERE, 'fixtures', 'train_cut.xplane.pb')


@pytest.fixture(scope='module')
def trace():
    return rt.Trace.from_file(FIXTURE)


def test_fixture_has_the_chips_planes_and_lines(trace):
    assert sorted(trace.device_ops) == [0]           # /device:TPU:0
    assert 'python3' in trace.host_lines             # /host:CPU
    assert trace.host_span(rt.TRACED_SPAN) == trace.window()
    names = {rt.short_name(n) for n, _, _ in trace.device_ops[0]}
    assert any(n.startswith('jvp_flash_fwd_') for n in names)
    assert all(' ' not in n and len(n) <= 80 for n in names)


def test_busy_is_a_union_and_self_times_add_up_to_it(trace):
    ops = rt.clip(trace.device_ops[0], trace.window())
    lo, hi = trace.window()
    # brute force on a 1 us grid
    ticks = int((hi - lo) / 1e3)
    mark = bytearray(ticks)
    for _, s, e in ops:
        a, b = int((s - lo) / 1e3), int((e - lo) / 1e3)
        mark[a:b] = b'\x01' * (b - a)
    busy = rt.busy_ns(ops)
    assert busy == pytest.approx(sum(mark) * 1e3, rel=5e-3)
    assert busy < sum(e - s for _, s, e in ops)      # events nest
    own = rt.self_times(ops)
    assert sum(ns for ns, _ in own.values()) == pytest.approx(busy)
    summary = rt.summary(trace)
    assert summary['window_s'] == pytest.approx(0.06)
    assert 0 < summary['busy_s'] <= summary['window_s']
    assert len(summary['breakdown']['device_ops']) <= 10
    top = dict(map(tuple, summary['breakdown']['device_ops']))
    assert 'jvp_flash_fwd_' in top                   # layers merged


def test_kernel_time_and_roofline_share(trace):
    ops = rt.clip(trace.device_ops[0], trace.window())
    ns, calls = rt.kernel_ns(ops, 'flash_fwd')
    assert calls == 8                                # one a layer
    per_call = ns / calls / 1e9
    model = {'hidden_size': 2048, 'num_heads': 16}
    need_ops, need_bytes = flops.flash_fwd_call(model, 4, 2048)
    least, bound = flops.least_seconds(
        need_ops, need_bytes, flops.peaks('TPU v5 lite'))
    assert bound == 'compute'
    assert least == pytest.approx(348.8e-6, rel=1e-3)
    assert 0.15 < least / per_call < 0.25            # 19.7% on the chip


def test_by_hand():
    ev = [('%while.1 = x', 0, 100), ('%fusion.3 = y', 10, 40),
          ('%fusion.4 = y', 50, 90), ('%copy.2 = z', 150, 170)]
    assert rt.union(ev) == [[0, 100], [150, 170]]
    assert rt.busy_ns(ev) == 120
    own = rt.self_times(ev)
    assert own == {'while.1': [30, 1], 'fusion.3': [30, 1],
                   'fusion.4': [40, 1], 'copy.2': [20, 1]}
    host = [('bench.engine_run', 0, 200), ('np.asarray', 95, 140)]
    gaps = rt.idle_gaps(ev, host, (0, 200))
    assert gaps == {'np.asarray': 50, 'bench.engine_run': 30}
    assert rt.stem('jvp_flash_fwd_.12') == 'jvp_flash_fwd_'
    assert rt.stem('fusion.12') == 'fusion'
    assert rt.stem('copy-done') == 'copy-done'
    t = rt.Trace({0: ev, 1: [('%a.1 = b', 0, 60)]},
                 {'main': [('bench.traced', 0, 200)] + host})
    s = rt.summary(t)
    assert s['busy_s'] == pytest.approx((120 + 60) / 2 / 1e9)
    assert s['window_s'] == pytest.approx(200e-9)


def test_an_unknown_device_is_an_error():
    assert flops.peaks('TPU v5 lite')['bf16_flops_per_s'] == 197e12
    with pytest.raises(KeyError):
        flops.peaks('cpu')


def test_train_operations_per_token():
    model = {'hidden_size': 2048, 'num_layers': 8,
             'published_vocab_size': 50257}
    fwd = 8 * (24 * 2048 ** 2 + 2 * 2048 * 2048) + 2 * 2048 * 50257
    assert flops.train_flops_per_token(model, 2048) == 3 * fwd
