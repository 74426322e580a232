"""The serving engine's spans carry the work they send (PR 37): a tiny
engine of each cache kind run under one `jax.profiler` session on the
CPU writes `serve.decode_dispatch`, `serve.prefill_dispatch`,
`serve.absorb` and `serve.first_token_sync` events whose arguments,
summed over the run, are the engine's own counters (and with no
session open, none is computed); the KV blocks a dispatch reads are
what its module's scan reads step by step (a loop written here from
the decode module's rules, not from the engine's arithmetic);
`telemetry.span`
hands its attrs to the annotation; `serve_step` says how much of an
intervention was the host's wait for the device."""
import math

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.serving.scheduler import Request

from test_tracing_spans import tiny_engine

SPANS = ('serve.decode_dispatch', 'serve.prefill_dispatch', 'serve.absorb',
         'serve.first_token_sync')
NAMED = {'serve.decode_dispatch': {'dispatch', 'batch', 'rows', 'steps',
                                   'ahead'},
         'serve.prefill_dispatch': {'dispatch', 'rows', 'tokens', 'padded'},
         'serve.absorb': {'dispatch', 'tokens'},
         'serve.first_token_sync': {'dispatch'}}


def load(shapes):
    rng = np.random.default_rng(0)
    return [Request(f'r{i}', rng.integers(0, 120, n), new, arrival_t=0.0)
            for i, (n, new) in enumerate(shapes)]


def traced_run(tmp_path, engine, requests):
    """{span name: [arguments]} in the order the spans began, the plans
    the engine sent, and run()'s report."""
    from jax.profiler import ProfileData
    from benchmark import reduce_trace
    engine.warmup()
    plans, decode = [], engine._decode

    def recording(plan):
        plans.append(plan)
        return decode(plan)

    engine._decode = recording
    jax.profiler.start_trace(str(tmp_path))
    try:
        report = engine.run(requests)
    finally:
        jax.profiler.stop_trace()
    found = []
    for plane in ProfileData.from_file(
            reduce_trace.find_xplane(str(tmp_path))).planes:
        for line in plane.lines:
            found += [(ev.start_ns, ev.name, dict(ev.stats))
                      for ev in line.events if ev.name in SPANS]
    out = {name: [] for name in SPANS}
    for _, name, args in sorted(found, key=lambda t: t[0]):
        out[name].append(args)
    return out, plans, report


def scan_blocks(plan, block_size, window=None):
    """The blocks a decode module reads for `plan`, by its scan: a
    row's view has length ctx + 1 at every step; an active row's ctx
    grows by one a step while it stays under its limit; the kernel
    reads a row's blocks up to its length, at least one and at most
    the table, from the block of the first position a window shows."""
    width = plan.tables.shape[-1]
    ctx = [int(c) for c in plan.ctx]
    active = [bool(a) for a in plan.active]
    total = 0
    for _ in range(plan.span):
        for i in range(plan.batch):
            length = ctx[i] + 1
            hi = min(max(math.ceil(length / block_size), 1), width)
            lo = 0 if window is None else min(
                max(length - window, 0) // block_size, hi - 1)
            total += hi - lo
        for i in range(plan.batch):
            ctx[i] += active[i]
            active[i] = active[i] and ctx[i] < plan.limit[i]
    return total


def total(args, name):
    return sum(a[name] for a in args)


def check_common(spans, engine, report, requests):
    """What every cache kind's spans carry, against the counters."""
    for name, keys in NAMED.items():
        assert spans[name], name
        for args in spans[name]:
            assert keys <= set(args), (name, args)
    dec, pre, ab, sync = (spans[n] for n in SPANS)
    assert [a['dispatch'] for a in dec] == list(range(1, len(dec) + 1))
    assert [a['dispatch'] for a in pre] == list(range(1, len(pre) + 1))
    # each span read is absorbed once, in the order it was sent
    assert [a['dispatch'] for a in ab] == [a['dispatch'] for a in dec]
    assert len(dec) == engine.interventions
    assert total(dec, 'ahead') == engine.decode_dispatches_ahead > 0
    counts = engine.counts()
    assert total(pre, 'tokens') == counts['prefill_tokens'] \
        == sum(r.prompt.size for r in requests)
    assert total(pre, 'padded') == counts['prefill_padded_tokens']
    # a chunk's first tokens are waited for once, under its number
    assert [a['dispatch'] for a in sync] == [a['dispatch'] for a in pre]
    assert total(pre, 'rows') == len(requests)
    # no preemption here: the first tokens and the spans' tokens are
    # every token delivered
    assert report['counters'].get('preempted', 0) == 0
    assert total(ab, 'tokens') + len(requests) == engine.decoded_tokens
    for args in dec:
        assert args['rows'] <= args['batch'] and args['steps'] == \
            engine.config.decode_span


def test_paged_pool_spans_carry_what_the_counters_count(tmp_path):
    telemetry.reset()
    engine = tiny_engine()
    requests = load([(5, 7), (12, 3), (7, 9), (15, 6), (3, 4), (9, 8)])
    spans, plans, report = traced_run(tmp_path, engine, requests)
    check_common(spans, engine, report, requests)
    dec = spans['serve.decode_dispatch']
    bs = engine.config.block_size
    assert [a['kv_blocks'] for a in dec] == [scan_blocks(p, bs)
                                             for p in plans]
    # the host counter counts every step at the span's end: never less,
    # and at most one block a row and step more
    read = total(dec, 'kv_blocks')
    assert read <= engine.kv_blocks_read \
        <= read + sum(a['batch'] * a['steps'] for a in dec)
    assert engine.kv_blocks_read > read
    assert not any(k.startswith('serve.')
                   for k in telemetry.get_recorder().counters)


def test_two_group_cache_and_a_routed_model(tmp_path):
    """By group, under the cache's own counter names, the window
    group's reads starting at the first block a query sees; the
    routed layers' counts of a span on its absorb."""
    from paddle_tpu.models import routed_window as rw
    paddle.seed(0)
    model = rw.routed_window_tiny()
    engine = ServingEngine(model, ServeConfig(
        block_size=4, max_slots=4, decode_span=2,
        prompt_buckets=(8, 16, 32), batch_buckets=(4,), prefill_batch=1,
        max_model_len=64, num_blocks=40))
    requests = load([(5, 9), (13, 6), (30, 12), (20, 8), (8, 5)])
    spans, plans, report = traced_run(tmp_path, engine, requests)
    check_common(spans, engine, report, requests)
    dec, ab = spans['serve.decode_dispatch'], spans['serve.absorb']
    bs, window = engine.config.block_size, engine.cache.window
    assert [a['kv_blocks_read_full'] for a in dec] == \
        [scan_blocks(p, bs) for p in plans]
    assert [a['kv_blocks_read_window'] for a in dec] == \
        [scan_blocks(p, bs, window) for p in plans]
    assert any(a['kv_blocks_read_window'] < a['kv_blocks_read_full']
               for a in dec)
    # the host counter reads every step at the span's end: more blocks
    # of the full group, and the window's first block further on
    counts = engine.counts()
    assert total(dec, 'kv_blocks_read_full') \
        < counts['kv_blocks_read_full']
    assert engine.step_stat_names
    for name in engine.step_stat_names:
        assert total(ab, name) == counts[name] > 0


def test_recurrent_state_spans_count_the_states_rewritten(tmp_path):
    from paddle_tpu.models.retention import retention_tiny
    paddle.seed(3)
    engine = ServingEngine(retention_tiny(), ServeConfig(
        max_slots=2, decode_span=4, prompt_buckets=(16, 32),
        batch_buckets=(2,), prefill_batch=1, max_model_len=64,
        temperature=0.0))
    requests = load([(9, 6), (25, 9), (14, 5)])
    spans, _plans, report = traced_run(tmp_path, engine, requests)
    check_common(spans, engine, report, requests)
    dec = spans['serve.decode_dispatch']
    assert all('kv_blocks' not in a for a in dec)
    assert total(dec, 'state_rows') == report['state_rows_updated'] \
        == sum(new - 1 for new in (6, 9, 5))


def test_hybrid_spans_count_blocks_and_states(tmp_path):
    """A model of state and attention layers: a dispatch carries both
    the blocks a kv layer reads, step by step, and the states it
    rewrites."""
    from paddle_tpu.models.granite_hybrid import granite_hybrid_tiny
    paddle.seed(3)
    engine = ServingEngine(granite_hybrid_tiny(), ServeConfig(
        block_size=4, max_slots=2, decode_span=4, prompt_buckets=(16, 32),
        batch_buckets=(2,), prefill_batch=1, max_model_len=64,
        temperature=0.0))
    requests = load([(9, 6), (25, 9), (14, 5)])
    spans, plans, report = traced_run(tmp_path, engine, requests)
    check_common(spans, engine, report, requests)
    dec = spans['serve.decode_dispatch']
    assert [a['kv_blocks'] for a in dec] == \
        [scan_blocks(p, engine.config.block_size) for p in plans]
    assert total(dec, 'state_rows') == report['state_rows_updated'] \
        == sum(new - 1 for new in (6, 9, 5))


def test_a_span_hands_its_arguments_to_the_annotation(tmp_path):
    """Given at the open and by `set` inside; a span without any is
    the plain annotation; with telemetry on, the record carries them
    too."""
    from jax.profiler import ProfileData
    from benchmark import reduce_trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span('serve.plan'):
            pass
        with telemetry.span('serve.absorb', dispatch=3) as sp:
            sp.set(tokens=7)
    finally:
        jax.profiler.stop_trace()
    seen = {ev.name: dict(ev.stats)
            for plane in ProfileData.from_file(reduce_trace.find_xplane(
                str(tmp_path))).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith('serve.')}
    assert seen == {'serve.plan': {},
                    'serve.absorb': {'dispatch': 3, 'tokens': 7}}
    rec = telemetry.Recorder()
    with rec.span('serve.absorb', dispatch=4) as sp:
        sp.set(tokens=2)
    (event,) = rec.events('span')
    assert (event['dispatch'], event['tokens']) == (4, 2)


@pytest.mark.parametrize('drained', [False, True])
def test_serve_step_splits_an_intervention_into_wait_and_host(drained):
    """Every serve_step in the flight ring says how long the host
    waited for the device (`sync_ms`) and how long it worked
    (`host_ms`); together they are the intervention up to the event."""
    telemetry.reset()
    engine = tiny_engine()
    engine.warmup()
    for req in load([(5, 7), (9, 4)]):
        engine.submit(req)
    while engine.scheduler.queue or engine.scheduler.running:
        engine.step()
        if drained:
            engine.drain()
    engine.drain()
    steps = telemetry.events('serve_step')
    assert steps
    for ev in steps:
        assert ev['sync_ms'] >= 0 and ev['host_ms'] >= 0
    assert sum(ev['sync_ms'] for ev in steps) > 0


def test_no_argument_is_computed_while_nobody_traces(monkeypatch):
    """With no profiler session open the spans are opened bare: the
    blocks a dispatch reads are never counted for them, and the engine
    serves as it did."""
    telemetry.reset()
    engine = tiny_engine()
    engine.warmup()

    def refuse(plan):
        raise AssertionError('span_reads with no profiler session')

    monkeypatch.setattr(engine.cache, 'span_reads', refuse)
    report = engine.run(load([(5, 7), (12, 3), (7, 9)]))
    assert report['counters'].get('preempted', 0) == 0
    assert engine.decoded_tokens == 7 + 3 + 9
