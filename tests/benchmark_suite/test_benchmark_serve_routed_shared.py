"""The serve_routed_shared runner end to end at a tiny size on the CPU,
through tiny_backlog_reasoning (answers past the tiny window): the
result line's shape, a `correct` that the profiler does not flip and
that turns false when the reference disagrees, under the control (the
reference in the precision below in the program's place) and under
each of the fifteen faults planted in the program; afmoe_flops against
counts by hand, and the configuration's arithmetic."""
import math
import os
import time

import pytest

from bench_helpers import HERE, REPO
import afmoe_faults

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device', 'compared'}
CELL = 'serve_backlog_moe_shared_decode'
COUNTED = {name + '.moe_shared' for name in (
    'kv_held_share', 'expert_load_max_over_mean', 'experts_hit_share',
    'compiles_in_window', 'batch_occupancy', 'preemptions',
    'intervention_ms')}


def tiny_cell():
    from benchmark import harness
    cell = harness.load_cell(CELL)
    cell['config'] = harness.load_json(os.path.join(
        HERE, 'configs', 'tiny_serve_routed_shared.json'))
    cell['traffic'] = harness.load_json(os.path.join(
        HERE, 'traffic', 'tiny_backlog_reasoning.json'))
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
    assert {'probe_logit_gap', 'probe_not_best', 'attn_full_rel', 'attn_window_rel',
            'moe_rel', 'expert_flips', 'served_logit_gap'} \
        <= set(line['compared'])
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
        assert 0 < value['kv_held_share.moe_shared'] < 100
        # the metrics' files scale by the cell's 128 experts in 4 routed
        # layers; here are 8 in 4
        assert 1.0 <= value['expert_load_max_over_mean.moe_shared'] / 16 \
            <= 8.0
        assert 0 < value['experts_hit_share.moe_shared'] * 16 <= 100
        assert value['compiles_in_window.moe_shared'] == 0


def test_a_reference_that_disagrees_turns_correct_false():
    line = run_tiny(seconds=0.5, reference_perturb=0.05)
    assert line['correct'] is False and line['failed'] == 0


@pytest.mark.parametrize('fault', afmoe_faults.FAULTS)
def test_a_planted_fault_turns_correct_false(fault):
    """The model reaches its parts through its module when the engine's
    modules are traced, so a broken one is what the engine runs."""
    restore = afmoe_faults.plant(fault)
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
    from benchmark.runners import serve_routed_shared as runner
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


def test_run_takes_its_four_parts_as_parameters():
    """What lets one `run` serve every serving runner: another `probe`
    in this one's place decides `correct`."""
    called = []

    def probe(config, engine, weights, logits_at, seed, say, compared,
              tap):
        called.append(tap)
        return False

    line = run_tiny(seconds=0.3, probe=probe)
    from benchmark.runners import serve_routed_shared as runner
    assert called == [runner.tap] and line['correct'] is False


MODEL = {'hidden_size': 8, 'head_dim': 4, 'num_heads': 6, 'num_kv_heads': 2,
         'intermediate_size': 5, 'dense_intermediate_size': 9,
         'num_experts': 7, 'experts_per_token': 3, 'num_shared_experts': 1,
         'num_layers': 5, 'num_dense_layers': 1,
         'window_layout': [1, 1, 0, 1, 1], 'published_vocab_size': 11}


def test_afmoe_flops_against_hand_counts():
    from benchmark import afmoe_flops as af
    assert af.layers_of(MODEL) == (1, 4)
    assert af.routed_layers(MODEL) == 4
    # q, o and the gate 8x24 each, k and v 8x8 each
    attention = 3 * 8 * 24 + 2 * 8 * 8
    assert af.attention_weights(MODEL) == attention
    # the router 8x7, three routed experts and the shared one
    routed = attention + 8 * 7 + 4 * 120
    assert af.routed_token_weights(MODEL) == routed
    dense = attention + 3 * 8 * 9
    assert af.dense_token_weights(MODEL) == dense
    assert af.token_weights(MODEL) == dense + 4 * routed
    positions = {'prefill_full': 10, 'prefill_window': 7,
                 'decode_full': 5, 'decode_window': 3}
    ops = af.window_ops(MODEL, prefill_tokens=4, decoded_tokens=2,
                        positions=positions)
    assert ops == (2 * (dense + 4 * routed) * 6 + 2 * 8 * 11 * 2
                   + 96 * (1 * 15 + 4 * 10))
    # the routed layers only, every expert's weights once a dispatch
    assert af.experts_prefill(MODEL, 10, 2) == (
        4 * 2 * 10 * 3 * 120, 4 * 2 * 7 * 120 * 2)


def test_the_published_sizes_give_the_issues_arithmetic():
    from benchmark import afmoe_flops as af, harness
    from benchmark import smallthinker_flops as sf
    from benchmark.reference import trinity_ref
    cfg = harness.load_json(os.path.join(
        REPO, 'benchmark/configs/trinity_mini_serve.json'))
    m = cfg['model']
    assert round(af.attention_weights(m) / 1e6, 2) == 27.26
    assert round(af.dense_token_weights(m) / 1e6, 1) == 65.0
    # a token meets 27.26 + 0.26 + 9 x 6.29 M in a routed layer
    assert round(af.routed_token_weights(m) / 1e6, 2) == 84.15
    count = sum(math.prod(s) for s in trinity_ref.shapes(m).values())
    assert count == cfg['weights']['parameters'] == 4241534720
    assert cfg['weights']['bytes'] == 2 * count + 2 * 4 * 128
    assert sf.kv_block_bytes(m, 16) == cfg['kv_pool'][
        'bytes_per_block_and_layer']
    full, window = af.layers_of(m)
    pool = cfg['kv_pool']
    assert (full, window) == (1, 4)
    assert pool['window_blocks'] == cfg['serve']['max_slots'] \
        * pool['window_bound'] + 1
    assert pool['bytes'] == 65536 * (full * pool['full_blocks']
                                     + window * pool['window_blocks'])
    hbm = harness.load_json(os.path.join(
        REPO, 'benchmark/peaks.json'))['TPU v5 lite']['hbm_bytes']
    resident = cfg['weights']['bytes'] + pool['bytes']
    assert 0.70 * hbm <= resident <= 15.75 * 2 ** 30 - 1e9
    # the cut: one dense and four routed layers, one whole period
    assert cfg['layer_types'] == [cfg['published']['layer_types'][i]
                                  for i in (0, 2, 3, 4, 5)]
    assert m['window_layout'] == [int(t == 'sliding_attention')
                                  for t in cfg['layer_types']]


def test_the_traffic_file_carries_the_issues_table():
    from benchmark import harness
    from benchmark.generators import requests
    traffic = harness.load_json(os.path.join(
        REPO, 'benchmark/traffic/backlog_reasoning.json'))
    about = traffic.pop('about')
    assert about and traffic == {
        'generator': 'requests', 'num_requests': 1024,
        'arrivals': {'kind': 'all_at_zero'},
        'prompt_len': {'kind': 'loguniform', 'lo': 128, 'hi': 2048},
        'new_tokens': {'kind': 'loguniform', 'lo': 512, 'hi': 4096},
        'max_context': 6144, 'id_limit': 200192, 'drain_s': 0}
    news = requests.lengths(traffic['new_tokens'], requests.STRATUM)
    assert 1650 < news.mean() < 1800 and (news > 2048).sum() >= 10
