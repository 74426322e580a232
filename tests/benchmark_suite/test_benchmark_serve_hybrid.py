"""The serve_hybrid runner end to end at a tiny size on the CPU, through
tiny_backlog_reasoning: the result line's shape, a `correct` that the
profiler does not flip and that turns false when the reference
disagrees, under each of the nine faults planted in the program and
under the state held in bfloat16 (the probe alone, on one model);
granite_flops against counts by hand; the configuration's arithmetic
and what it stands for; and the readers of the `.mamba` metrics that
read spans, on a trace drawn by hand and on traces whose spans carry
nothing."""
import math
import os
import time

import pytest

from bench_helpers import HERE, REPO
import hybrid_faults
from benchmark import harness, reduce_trace as rt, scoped_trace as sc

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device', 'compared'}
CELL = 'serve_backlog_mamba_hybrid'
COUNTED = {name + '.mamba' for name in (
    'compiles_in_window', 'batch_occupancy', 'preemptions',
    'intervention_ms', 'peak_hbm_gb')}


def tiny_cell():
    cell = harness.load_cell(CELL)
    cell['config'] = harness.load_json(os.path.join(
        HERE, 'configs', 'tiny_serve_hybrid.json'))
    cell['traffic'] = harness.load_json(os.path.join(
        HERE, 'traffic', 'tiny_backlog_reasoning.json'))
    return cell


def run_tiny(seed=2147495993, seconds=1.0, trace=0, **runner_kwargs):
    from benchmark import run
    return run.run_cell(tiny_cell(), seed, seconds, trace,
                        time.monotonic(), **runner_kwargs)


@pytest.mark.parametrize('how', ['plain', 'traced'])
def test_the_cell_runs_and_is_correct(how):
    line = run_tiny(seconds=1.0, trace=int(how == 'traced'))
    assert set(line) == KEYS
    assert list(line)[-1] == 'compared'
    assert {'probe_logit_gap', 'probe_not_best', 'state_rel', 'mamba_rel',
            'attn_rel', 'served_logit_gap'} <= set(line['compared'])
    assert all(v <= limit for v, limit in line['compared'].values()), \
        line['compared']
    assert line['correct'] is True
    assert line['failed'] == 0 and 0 < line['attempted'] < 2048
    assert line['device']['platform'] == 'cpu'
    names = set(line['metrics'])
    if how == 'plain':
        assert names == {'serve_tokens_per_s', 'setup_s'}
    else:
        # counters are read; nothing under a device metric's name (the
        # CPU's allocator reports no peak)
        assert names == COUNTED - {'peak_hbm_gb.mamba'}
        value = {n: m['value'] for n, m in line['metrics'].items()}
        assert value['compiles_in_window.mamba'] == 0
        # a pool of 40 blocks for rows of up to 88 positions: preempted
        assert value['preemptions.mamba'] > 0


def test_a_reference_that_disagrees_turns_correct_false():
    line = run_tiny(seconds=0.3, reference_perturb=0.05)
    assert line['correct'] is False and line['failed'] == 0


def test_run_is_the_shared_runners_with_this_models_parts():
    """No copy of `run`: the parts handed in are the ones it builds and
    probes with (a `build` that stops it says which cell it was given:
    the mix's ids cut to the model's vocabulary)."""
    from benchmark.runners import serve_hybrid as runner

    class Built(Exception):
        pass

    def build(config, seed, clock):
        raise Built(config)

    cell = tiny_cell()
    cell['traffic']['id_limit'] = 100000
    with pytest.raises(Built):
        runner.run(cell, 1, 0.1, False, time.monotonic(), print,
                   build=build)
    assert runner.in_vocabulary(cell)['traffic']['id_limit'] == 128
    assert cell['traffic']['id_limit'] == 100000


@pytest.fixture(scope='module')
def built():
    """The tiny model and its weights, once: each case below serves it
    through an engine of its own, whose modules are traced anew."""
    from benchmark.runners import serve_hybrid as runner
    config = tiny_cell()['config']
    model, _engine, weights = runner.build(config, 7, time.monotonic)
    return config, model, weights, runner.reference(config, weights)


def _probe(built):
    from paddle_tpu.serving import ServeConfig, ServingEngine
    from benchmark.runners import serve_hybrid as runner
    config, model, weights, logits_at = built
    engine = ServingEngine(model, ServeConfig(**config['serve']))
    compared = {}
    ok = runner.probe(config, engine, weights, logits_at, 7,
                      lambda msg: None, compared)
    return ok, compared


@pytest.mark.parametrize('fault', hybrid_faults.FAULTS)
def test_a_planted_fault_turns_the_probe_false(built, fault):
    """The model reaches its parts through its module when the engine's
    modules are traced, so a broken one is what the engine runs."""
    restore = hybrid_faults.plant(fault)
    try:
        ok, compared = _probe(built)
    finally:
        restore()
    over = {k: v for k, v in compared.items() if v[0] > v[1]}
    assert ok is False and over, compared


@pytest.mark.parametrize('control', hybrid_faults.CHIP_CONTROLS)
def test_a_chip_control_computes_exactly_on_the_cpu(built, control):
    """A control the chip control reads (a part at a precision a TPU may
    lower) is planted where the engine's modules reach it, and on the
    CPU, which computes it in float32, the probe stays true."""
    restore = hybrid_faults.plant(control)
    try:
        ok, compared = _probe(built)
    finally:
        restore()
    assert ok is True, compared


def test_a_state_held_in_bfloat16_turns_the_probe_false(built,
                                                         monkeypatch):
    """The control of `probe.state_rel_tol`: the precision below the one
    the configuration states.  The held state's distance from its
    definition reads some ten times the limit, where the float32 state
    stays a hundred times under it."""
    import jax.numpy as jnp
    from paddle_tpu.serving.kv_cache import RecurrentStateCache
    ok, sound = _probe(built)
    monkeypatch.setattr(RecurrentStateCache, 'dtype', jnp.bfloat16)
    broken_ok, broken = _probe(built)
    assert ok is True and broken_ok is False
    err, tol = sound['state_rel']
    assert err < tol / 30
    err, tol = broken['state_rel']
    assert err > 10 * tol


# -- the counts ------------------------------------------------------------------------
MODEL = {'hidden_size': 8, 'mamba_n_heads': 2, 'mamba_d_head': 3,
         'mamba_d_state': 4, 'head_dim': 2, 'num_heads': 4,
         'num_kv_heads': 2, 'intermediate_size': 5,
         'layer_types': ['mamba', 'attention', 'mamba'],
         'published_vocab_size': 11}


def test_granite_flops_against_hand_counts():
    from benchmark import granite_flops as gf
    assert gf.layers_of(MODEL) == (2, 1)
    # the state [4, 6] in and out, x and y 6, B and C 4, dt 2; 5 an
    # element of the state
    assert gf.ssm_decode_update(MODEL) == (5 * 4 * 6,
                                           4 * (48 + 12 + 8 + 2))
    # Mamba: in_proj 8 x (2 x 6 + 2 x 4 + 2), out_proj 6 x 8; attention
    # q and o 8 x 8, k and v 8 x 4; three MLPs of 3 x 8 x 5
    assert gf.token_weights(MODEL) == 2 * (176 + 48) + 192 + 3 * 120
    assert gf.kv_block_bytes(MODEL, 16) == 2 * 16 * 2 * 2 * 4
    assert gf.paged_read(MODEL, 16, 10) == (4 * 4 * 2 * 10 * 16,
                                            10 * 512)
    ops = gf.window_ops(MODEL, prefill_tokens=10, decoded_tokens=3,
                        positions={'prefill_full': 55, 'decode_full': 40})
    assert ops == 2 * 1000 * 13 + 2 * 88 * 3 + 2 * 120 * 13 + 32 * 95


GRANITE = harness.load_json(os.path.join(
    REPO, 'benchmark', 'configs', 'granite_4_0_h_micro_serve.json'))


def test_the_configuration_is_the_published_model_whole():
    """Every key of the catalog's config at the top level as published,
    nothing in `reduced`, every width and all 40 layers in `model`."""
    row = GRANITE['published']
    assert GRANITE['reduced'] == []
    for key, value in row.items():
        if not key.startswith('n_'):
            assert GRANITE[key] == value, key
    m = GRANITE['model']
    assert m['layer_types'] == row['layer_types']
    assert [i for i, t in enumerate(m['layer_types'])
            if t == 'attention'] == [5, 15, 25, 35]
    for key in ('hidden_size', 'num_hidden_layers', 'intermediate_size',
                'mamba_expand', 'mamba_n_heads', 'mamba_d_head',
                'mamba_d_state', 'mamba_n_groups', 'mamba_d_conv',
                'mamba_chunk_size', 'embedding_multiplier',
                'residual_multiplier', 'logits_scaling',
                'attention_multiplier', 'rms_norm_eps'):
        assert {**m, 'num_hidden_layers': m['num_layers']}[key] \
            == row[key], key
    assert (m['num_heads'], m['num_kv_heads'], m['head_dim']) == (
        row['num_attention_heads'], row['num_key_value_heads'],
        row['hidden_size'] // row['num_attention_heads'])
    assert m['vocab_size'] == m['published_vocab_size'] == row['vocab_size']


def test_the_published_sizes_give_the_configurations_arithmetic():
    from benchmark.reference import granite_ref
    m = GRANITE['model']
    shapes = granite_ref.shapes(m)
    count = sum(math.prod(s) for s in shapes.values())
    assert count == GRANITE['weights']['parameters'] == 3191396096
    # bfloat16, the 36 layers' three float32 scalars of 64 heads
    scalars = 36 * 3 * 64
    assert GRANITE['weights']['bytes'] == 2 * count + 2 * scalars
    state, pool, serve = (GRANITE[k] for k in ('state', 'kv_pool', 'serve'))
    slot = 36 * (128 * 4096 + 3 * 4352) * 4
    assert state['bytes_per_slot'] == slot
    assert state['bytes'] == slot * state['slots'] == slot * 64
    assert state['slots'] == serve['max_slots']
    assert pool['num_blocks'] == serve['num_blocks']
    assert pool['bytes_per_block_and_layer'] == 16 * 8 * 64 * 2 * 4
    assert pool['bytes'] == pool['num_blocks'] * 65536 * 4
    hbm = harness.load_json(os.path.join(
        REPO, 'benchmark/peaks.json'))['TPU v5 lite']['hbm_bytes']
    resident = GRANITE['weights']['bytes'] + state['bytes'] + pool['bytes']
    assert 0.75 * hbm <= resident <= 15.75 * 2 ** 30 - 1e9
    assert serve['max_model_len'] == 6144


# -- the readers of the spans, on a trace drawn by hand ---------------------------------
MS = 1e6                # the trace's clock is in ns
SSM = ('jit(decode_fn)/serve.decode/while/body/dec.mamba/ssm.decode/'
       'ssm_decode/pallas_call:')
PAGED = ('jit(decode_fn)/serve.decode/while/body/dec.attn/paged.attention/'
         'paged_decode_grouped/pallas_call:')
SCAN = 'jit(prefill_fn)/serve.prefill/dec.mamba/ssm.prefill/dot:'


def by_hand():
    """A window of 100 ms.  Decode dispatches 2 and 3 run whole in it
    (1 was in flight as it opened, 4 outlasts it).  Prefill 2 runs whole
    in it."""
    spans = [
        ('serve.decode_dispatch', -5 * MS, -4 * MS,
         {'dispatch': 1, 'ahead': 0, 'steps': 8, 'kv_blocks': 9999,
          'state_rows': 9999}),
        ('serve.decode_dispatch', 10 * MS, 11 * MS,
         {'dispatch': 2, 'ahead': 1, 'steps': 8, 'kv_blocks': 1000,
          'state_rows': 8}),
        ('serve.absorb', 26 * MS, 27 * MS, {'dispatch': 1, 'tokens': 8}),
        ('serve.prefill_dispatch', 45 * MS, 46 * MS,
         {'dispatch': 2, 'rows': 1, 'tokens': 100, 'padded': 128,
          'attn_pairs': 5050}),
        ('serve.decode_dispatch', 40 * MS, 41 * MS,
         {'dispatch': 3, 'ahead': 1, 'steps': 8, 'kv_blocks': 1200,
          'state_rows': 8}),
        ('serve.absorb', 51 * MS, 52 * MS, {'dispatch': 2, 'tokens': 8}),
        ('serve.first_token_sync', 52 * MS, 61 * MS, {'dispatch': 2}),
        ('serve.decode_dispatch', 70 * MS, 71 * MS,
         {'dispatch': 4, 'ahead': 1, 'steps': 8, 'kv_blocks': 1400,
          'state_rows': 16}),
        ('serve.absorb', 101 * MS, 102 * MS, {'dispatch': 3, 'tokens': 8}),
    ]
    runs = {'jit_decode_fn': [(-3 * MS, 25 * MS), (30 * MS, 44 * MS),
                              (62 * MS, 80 * MS), (90 * MS, 115 * MS)],
            'jit_prefill_fn': [(50 * MS, 60 * MS)]}
    ops = [('%ssm_decode.1 = custom-call()', -3 * MS, 5 * MS, SSM),
           ('%ssm_decode.2 = custom-call()', 30 * MS, 36 * MS, SSM),
           ('%paged_decode_grouped.3 = custom-call()', 36 * MS, 40 * MS,
            PAGED),
           ('%fusion.4 = fusion()', 50 * MS, 58 * MS, SCAN),
           ('%ssm_decode.5 = custom-call()', 62 * MS, 72 * MS, SSM),
           ('%paged_decode_grouped.6 = custom-call()', 72 * MS, 77 * MS,
            PAGED),
           ('%ssm_decode.7 = custom-call()', 92 * MS, 112 * MS, SSM)]
    host = [(rt.TRACED_SPAN, 0.0, 100 * MS)] + [s[:3] for s in spans]
    st = sc.ScopedTrace({0: ops}, sorted(host, key=lambda t: t[1]),
                        path='by_hand_hybrid.xplane.pb')
    return {'scoped_trace': st, 'span_args': {st.path: (spans, runs)},
            'config': GRANITE, 'device_kind': 'TPU v5 lite'}


def read_metric(name, ctx):
    spec = harness.load_json(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name + '.json'))
    return harness.read_layer_metrics(
        [{'name': name, 'unit': '-', **spec}], ctx).get(name)


# a live row's update of one layer: the state [128, 4096] in and out,
# x and y 4096, B and C 128, dt 64, float32
UPDATE_BYTES = 4 * (2 * 128 * 4096 + 2 * 4096 + 2 * 128 + 64)
EXPECTED = {
    # 8 + 8 rows x 36 Mamba layers at 819 GB/s over 6 + 10 ms
    'ssm_decode_roofline.mamba':
        100 * 16 * 36 * UPDATE_BYTES / 819e9 / 16e-3,
    'ssm_decode_ms_per_token_step.mamba': 16 / 16,
    'ssd_prefill_ms_per_dispatch.mamba': 8.0,
    # 2,200 blocks of 16 x 8 heads x 64 x 2 x 4 B in 4 layers, 4 + 5 ms
    'paged_decode_roofline.mamba':
        100 * 2200 * 4 * 65536 / 819e9 / 9e-3,
    'paged_attention_ms_per_token_step.mamba': 9 / 16,
    # the dispatches begun in the window: 2, 3 and 4
    'state_rows_per_token_step.mamba': 32 / 24,
    'decode_sent_ahead_share.mamba': 100.0,
}


@pytest.mark.parametrize('name', sorted(EXPECTED))
def test_each_reader_counts_the_dispatches_the_window_holds(name):
    got = read_metric(name, by_hand())
    assert got is not None
    assert got['value'] == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize('fixture', ['chat_scoped_cut.xplane.pb',
                                     'train_cut.xplane.pb'])
@pytest.mark.parametrize('name', sorted(EXPECTED))
def test_left_out_where_the_spans_carry_nothing(name, fixture):
    """The parent's traces: spans without these arguments, or no spans
    at all; and a run without a chip trace: nothing read, nothing
    raised."""
    ctx = {'scoped_trace': sc.ScopedTrace.from_file(
               os.path.join(HERE, 'fixtures', fixture)),
           'config': GRANITE, 'device_kind': 'TPU v5 lite'}
    assert read_metric(name, ctx) is None
    assert read_metric(name, {'trace': None, 'counters': {},
                              'config': GRANITE}) is None


def test_step_mfu_is_the_windows_operations_over_the_peak():
    """On the chip's counters: some 2,500 tokens/s decoded and 500
    prefilled against 3.19 G weights a token is a few percent of
    197 TFLOP/s; nothing on the CPU."""
    from benchmark.readers import granite_step_mfu
    counters = {'window_ms': 45e3, 'prefill_tokens': 22500,
                'decoded_tokens': 112500,
                'context_positions': {'prefill_full': 44 * 512 ** 2 // 2,
                                      'decode_full': 112500 * 1800}}
    ctx = {'counters': counters, 'on_tpu': True, 'chips': 1,
           'device_kind': 'TPU v5 lite', 'config': GRANITE}
    assert 8 < granite_step_mfu.read({}, ctx) < 12
    assert granite_step_mfu.read({}, dict(ctx, on_tpu=False)) is None
    assert granite_step_mfu.read({}, dict(ctx, counters={})) is None
