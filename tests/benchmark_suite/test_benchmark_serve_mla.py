"""The serve_mla runner end to end at a tiny size on the CPU, through
tiny_backlog_longdoc: the result line's shape, a `correct` that the
profiler does not flip and that turns false when the reference
disagrees, under the control (the reference in the precision below in
the program's place) and under each of the eight faults planted in the
program; mla_flops against counts by hand; the configuration's and the
traffic's arithmetic; and the four readers of the `.mla` metrics that
read spans, on a trace drawn by hand and on traces whose spans carry
nothing."""
import os
import time

import pytest

from bench_helpers import HERE, REPO
import mla_faults
from benchmark import harness, reduce_trace as rt, scoped_trace as sc

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device', 'compared'}
CELL = 'serve_backlog_mla_longdoc'
COUNTED = {name + '.mla' for name in (
    'experts_hit_share', 'compiles_in_window', 'batch_occupancy',
    'preemptions', 'intervention_ms')}


def tiny_cell():
    cell = harness.load_cell(CELL)
    cell['config'] = harness.load_json(os.path.join(
        HERE, 'configs', 'tiny_serve_mla.json'))
    cell['traffic'] = harness.load_json(os.path.join(
        HERE, 'traffic', 'tiny_backlog_longdoc.json'))
    return cell


def run_tiny(seed=2147495993, seconds=1.0, trace=0, **runner_kwargs):
    from benchmark import run
    return run.run_cell(tiny_cell(), seed, seconds, trace,
                        time.monotonic(), **runner_kwargs)


@pytest.mark.parametrize('how', ['plain', 'traced'])
def test_the_cell_runs_and_is_correct(how):
    line = run_tiny(seconds=1.5, trace=int(how == 'traced'))
    assert set(line) == KEYS
    assert list(line)[-1] == 'compared'
    assert {'probe_logit_gap', 'probe_not_best', 'attn_dense_rel',
            'attn_routed_rel', 'moe_rel', 'expert_flips',
            'served_logit_gap'} <= set(line['compared'])
    assert all(v <= limit for v, limit in line['compared'].values()), \
        line['compared']
    assert line['correct'] is True
    assert line['failed'] == 0 and 0 < line['attempted'] < 2048
    assert line['device']['platform'] == 'cpu'
    names = set(line['metrics'])
    if how == 'plain':
        assert names == {'serve_tokens_per_s', 'setup_s'}
    else:
        # counters are read; nothing under a device metric's name
        assert names == COUNTED
        value = {n: m['value'] for n, m in line['metrics'].items()}
        # the file scales by the cell's 32 held experts in 7 routed
        # layers; here 4 in 3
        assert 0 < value['experts_hit_share.mla'] * 4 * 3 / (32 * 7) <= 100
        assert value['compiles_in_window.mla'] == 0
        # a pool of 48 blocks for rows of 4 slots: rows are preempted
        assert value['preemptions.mla'] > 0


def test_a_reference_that_disagrees_turns_correct_false():
    line = run_tiny(seconds=0.5, reference_perturb=0.05)
    assert line['correct'] is False and line['failed'] == 0


@pytest.mark.parametrize('fault', mla_faults.FAULTS)
def test_a_planted_fault_turns_correct_false(fault):
    """The model reaches its parts through its module when the engine's
    modules are traced, so a broken one is what the engine runs."""
    restore = mla_faults.plant(fault)
    try:
        line = run_tiny(seconds=0.3)
    finally:
        restore()
    over = {k: v for k, v in line['compared'].items() if v[0] > v[1]}
    assert line['correct'] is False and over, line['compared']
    assert line['failed'] == 0


def test_the_control_turns_the_tap_false():
    """The runner's own `tap`: the program's decode module passes, the
    reference with its matrices in float8 in its place fails, by each
    relative limit."""
    from benchmark.runners import serve_mla as runner
    config = tiny_cell()['config']
    _model, engine, weights = runner.build(config, 7, time.monotonic)
    verdicts, taps = {}, {}
    for name, lower in (('program', None), ('control', 'float8_e4m3fn')):
        taps[name] = {}
        verdicts[name] = runner.tap(config, engine, weights, 7,
                                    lambda msg: None, taps[name],
                                    weights_as=lower)
    assert verdicts == {'program': True, 'control': False}, taps
    assert all(v > limit for k, (v, limit) in taps['control'].items()
               if k != 'expert_flips'), taps


def test_run_is_the_shared_runners_with_this_models_parts():
    """No copy of `run`: another `probe` in this one's place decides
    `correct`, and it is handed this runner's `tap`."""
    called = []

    def probe(config, engine, weights, logits_at, seed, say, compared,
              tap):
        called.append(tap)
        return False

    line = run_tiny(seconds=0.3, probe=probe)
    from benchmark.runners import serve_mla as runner
    assert called == [runner.tap] and line['correct'] is False


# -- the counts ------------------------------------------------------------------------
MODEL = {'hidden_size': 8, 'num_heads': 2, 'q_lora_rank': 6,
         'kv_lora_rank': 4, 'qk_nope_head_dim': 3, 'qk_rope_head_dim': 2,
         'v_head_dim': 5, 'intermediate_size': 7,
         'dense_intermediate_size': 9, 'num_experts': 16,
         'experts_per_token': 4, 'num_shared_experts': 1,
         'held_experts': [4, 4], 'num_layers': 3, 'num_dense_layers': 1,
         'published_vocab_size': 11}


def test_mla_flops_against_hand_counts():
    from benchmark import mla_flops as mf
    # q_a 8x6, q_b 6x(2 x 5), kv_a 8x(4 + 2), kv_b 4x(2 x 8), o 10x8
    attention = 48 + 60 + 48 + 64 + 80
    assert mf.attention_weights(MODEL) == attention
    assert mf.expert_weights(MODEL) == 3 * 8 * 7
    # three layers' attention, the dense MLP, two routers and shared
    assert mf.token_weights(MODEL) == 3 * attention + 3 * 8 * 9 \
        + 2 * (8 * 16 + 168)
    assert mf.position_bytes(MODEL) == 6 * 4
    assert mf.latent_read(MODEL, 16, 10) == (0, 10 * 16 * 24 * 3)
    # a pair: 2 (3 + 2) + 2 x 5 a head, two heads, three layers
    assert mf.prefill_flash(MODEL, 7) == (7 * 2 * 20 * 3, 0)
    assert mf.experts_stream(MODEL, 5) == (0, 5 * 168 * 2)
    ops = mf.window_ops(MODEL, prefill_tokens=10, decoded_tokens=3,
                        decode_assignments=5, prefill_pairs=55,
                        decode_keys=40)
    prompt_experts = 10 * 2 * 4 * 4 / 16
    assert ops == (2 * (mf.token_weights(MODEL) * 13
                        + 168 * (5 + prompt_experts))
                   + 2 * 8 * 11 * 3 + 55 * 2 * 20 * 3
                   + 40 * 2 * 2 * (2 * 4 + 2) * 3)


def test_the_published_sizes_give_the_configurations_arithmetic():
    from benchmark import mla_flops as mf
    from benchmark.reference import joyai_ref
    import math
    cfg = harness.load_json(os.path.join(
        REPO, 'benchmark/configs/joyai_llm_flash_serve.json'))
    m = cfg['model']
    # attention a layer with its two low-rank norms, 26,347,520
    assert mf.attention_weights(m) + 1536 + 512 == 26347520
    assert mf.expert_weights(m) == 4718592
    shapes = joyai_ref.shapes(m)
    count = sum(math.prod(s) for s in shapes.values())
    assert count == cfg['weights']['parameters'] == 1878052608
    # bfloat16, the 7 routers' 256 biases in float32
    assert cfg['weights']['bytes'] == 2 * count + 2 * 7 * 256
    assert shapes['model.layers.1.experts.gate_proj'] == (32, 2048, 768)
    assert shapes['model.layers.1.router.weight'] == (2048, 256)
    pool, serve = cfg['kv_pool'], cfg['serve']
    assert pool['num_blocks'] == serve['num_blocks']
    assert pool['bytes_per_block_and_layer'] == 16 * 576 * 4
    assert pool['bytes'] == pool['num_blocks'] * 16 * pool['row'] * 4 * 8
    hbm = harness.load_json(os.path.join(
        REPO, 'benchmark/peaks.json'))['TPU v5 lite']['hbm_bytes']
    resident = cfg['weights']['bytes'] + pool['bytes']
    assert 0.70 * hbm <= resident <= 15.75 * 2 ** 30 - 1e9
    # the cut: 8 of 40 layers, 32 of 256 experts, no MTP module
    assert (cfg['num_hidden_layers'], cfg['n_routed_experts'],
            cfg['num_nextn_predict_layers']) == (8, 32, 0)
    assert cfg['published']['n_routed_experts'] == 256 \
        == cfg['published_num_experts'] == m['num_experts']
    assert m['held_experts'] == cfg['held_experts'] == [0, 32]
    assert serve['max_model_len'] == 16384 + 2048


def test_the_traffic_file_carries_its_table():
    from benchmark.generators import requests
    traffic = harness.load_json(os.path.join(
        REPO, 'benchmark/traffic/backlog_longdoc.json'))
    about = traffic.pop('about')
    assert about and traffic == {
        'generator': 'requests', 'num_requests': 1024,
        'arrivals': {'kind': 'all_at_zero'},
        'prompt_len': {'kind': 'loguniform', 'lo': 4096, 'hi': 16384},
        'new_tokens': {'kind': 'loguniform', 'lo': 512, 'hi': 2048},
        'max_context': 18432, 'id_limit': 129280, 'drain_s': 0}
    prompts = requests.lengths(traffic['prompt_len'], requests.STRATUM)
    assert 8000 < sorted(prompts)[16] < 8400


# -- the readers of the spans, on a trace drawn by hand ---------------------------------
MS = 1e6                # the trace's clock is in ns
KERNEL = ('jit(decode_fn)/serve.decode/while/body/dec.attn/mla.decode/'
          'paged_decode_latent/pallas_call:')
EXPERTS = 'jit(decode_fn)/serve.decode/while/body/dec.moe/moe.experts/dot:'
FLASH = 'jit(prefill_fn)/serve.prefill/dec.attn/mla.prefill/flash_fwd/p:'
JOYAI = harness.load_json(os.path.join(
    REPO, 'benchmark', 'configs', 'joyai_llm_flash_serve.json'))


def by_hand():
    """A window of 100 ms.  Decode dispatches 2 and 3 run whole in it
    (1 was in flight as it opened, 4 outlasts it); dispatch 3's absorb
    begins after the window closes.  Prefill 2 runs whole in it."""
    spans = [
        ('serve.decode_dispatch', -5 * MS, -4 * MS,
         {'dispatch': 1, 'ahead': 0, 'steps': 8, 'kv_blocks': 9999}),
        ('serve.decode_dispatch', 10 * MS, 11 * MS,
         {'dispatch': 2, 'ahead': 1, 'steps': 8, 'kv_blocks': 1000}),
        ('serve.absorb', 26 * MS, 27 * MS,
         {'dispatch': 1, 'tokens': 8, 'moe_experts_hit': 777}),
        ('serve.prefill_dispatch', 45 * MS, 46 * MS,
         {'dispatch': 2, 'rows': 1, 'tokens': 100, 'padded': 128,
          'attn_pairs': 5050}),
        ('serve.decode_dispatch', 40 * MS, 41 * MS,
         {'dispatch': 3, 'ahead': 1, 'steps': 8, 'kv_blocks': 1200}),
        ('serve.absorb', 51 * MS, 52 * MS,
         {'dispatch': 2, 'tokens': 8, 'moe_experts_hit': 100}),
        ('serve.first_token_sync', 52 * MS, 61 * MS, {'dispatch': 2}),
        ('serve.decode_dispatch', 70 * MS, 71 * MS,
         {'dispatch': 4, 'ahead': 1, 'steps': 8, 'kv_blocks': 1400}),
        ('serve.absorb', 101 * MS, 102 * MS,
         {'dispatch': 3, 'tokens': 8, 'moe_experts_hit': 120}),
    ]
    runs = {'jit_decode_fn': [(-3 * MS, 25 * MS), (30 * MS, 44 * MS),
                              (62 * MS, 80 * MS), (90 * MS, 115 * MS)],
            'jit_prefill_fn': [(50 * MS, 60 * MS)]}
    ops = [('%paged_decode_latent.1 = custom-call()', -3 * MS, 5 * MS,
            KERNEL),
           ('%paged_decode_latent.2 = custom-call()', 30 * MS, 36 * MS,
            KERNEL),
           ('%fusion.3 = fusion()', 36 * MS, 40 * MS, EXPERTS),
           ('%flash_fwd.4 = custom-call()', 50 * MS, 58 * MS, FLASH),
           ('%paged_decode_latent.5 = custom-call()', 62 * MS, 72 * MS,
            KERNEL),
           ('%fusion.6 = fusion()', 72 * MS, 77 * MS, EXPERTS),
           ('%paged_decode_latent.7 = custom-call()', 92 * MS, 112 * MS,
            KERNEL)]
    host = [(rt.TRACED_SPAN, 0.0, 100 * MS)] + [s[:3] for s in spans]
    st = sc.ScopedTrace({0: ops}, sorted(host, key=lambda t: t[1]),
                        path='by_hand_mla.xplane.pb')
    return {'scoped_trace': st, 'span_args': {st.path: (spans, runs)},
            'config': JOYAI, 'device_kind': 'TPU v5 lite'}


def read_metric(name, ctx):
    spec = harness.load_json(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name + '.json'))
    return harness.read_layer_metrics(
        [{'name': name, 'unit': '-', **spec}], ctx).get(name)


# the arithmetic over dispatches 2 and 3 (and prefill 2) alone
EXPECTED = {
    # 36,864 B a block and layer, 8 layers, 819 GB/s, over 6 + 10 ms
    'latent_decode_roofline.mla':
        100 * (1000 + 1200) * 36864 * 8 / 819e9 / 16e-3,
    # 6 + 10 ms of kernel under mla.decode over 16 token steps
    'latent_attention_ms_per_token_step.mla': 16 / 16,
    # 5,050 pairs x 32 heads x 2 x (192 + 128) x 8 layers at 197 TFLOP/s
    'mla_prefill_flash_roofline.mla':
        100 * 5050 * 32 * 2 * 320 * 8 / 197e12 / 8e-3,
    # 100 + 120 held experts of 9.44 MB over 4 + 5 ms at 819 GB/s
    'moe_decode_roofline.mla': 100 * 220 * 4718592 * 2 / 819e9 / 9e-3,
    # 8 ms of the prefill module's ops over its 100 true tokens, in us
    'prefill_us_per_token.mla': 8e6 / 100 / 1e3,
    'decode_sent_ahead_share.mla': 100.0,
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
           'config': JOYAI, 'device_kind': 'TPU v5 lite'}
    assert read_metric(name, ctx) is None
    assert read_metric(name, {'trace': None, 'counters': {},
                              'config': JOYAI}) is None


def test_step_mfu_is_the_windows_operations_over_the_peak():
    """On the chip's counters: some 1,000 tokens/s decoded and 3,000
    prefilled against 322 M weights a token and a head of 265 M is a
    few percent of 197 TFLOP/s; nothing on the CPU."""
    from benchmark.readers import mla_step_mfu
    counters = {'window_ms': 45e3, 'prefill_tokens': 135000,
                'decoded_tokens': 45000, 'moe_assignments': 45000 * 7,
                'context_positions': {'prefill_full': 16 * 8192 ** 2 // 2,
                                      'decode_full': 45000 * 9000}}
    ctx = {'counters': counters, 'on_tpu': True, 'chips': 1,
           'device_kind': 'TPU v5 lite', 'config': JOYAI}
    assert 1 < mla_step_mfu.read({}, ctx) < 10
    assert mla_step_mfu.read({}, dict(ctx, on_tpu=False)) is None
    assert mla_step_mfu.read({}, dict(ctx, counters={})) is None
