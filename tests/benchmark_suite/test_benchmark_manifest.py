"""BENCHMARK.json is well-formed and every file it names is there."""
import importlib
import json
import os
import re

import pytest

from bench_helpers import REPO

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter',
           'host_clock'}


@pytest.fixture(scope='module')
def manifest():
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        return json.load(f)


def cells_of(metric, manifest):
    return metric.get('workloads',
                      [c['name'] for c in manifest['workloads']])


def test_top_level_keys(manifest):
    assert set(manifest) == {'command', 'paths', 'run_seconds', 'configs',
                             'workloads', 'end_to_end', 'per_layer'}
    assert manifest['paths'] == ['benchmark', 'tests/benchmark_suite']
    assert isinstance(manifest['run_seconds'], int)
    assert 1 <= manifest['run_seconds'] <= 51
    # 2 + 14 runs a cell, with the full 24 cells, must fit the check
    rs = manifest['run_seconds']
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys(manifest):
    for cfg in manifest['configs']:
        assert set(cfg) == {'name', 'source', 'file', 'reduced', 'why'}
        assert NAME.match(cfg['name'])
        assert all(NAME.match(k) for k in cfg['reduced'])
        assert 1 <= len(cfg['why']) <= 200 and len(cfg['source']) <= 200
    for cell in manifest['workloads']:
        assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(cell['name']) and NAME.match(cell['traffic'])
        assert cell['chips'] in (1, 4)
        assert 1 <= len(cell['why']) <= 200
    for m in manifest['end_to_end']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better',
                                          'bound', 'source'}
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.1
    for m in manifest['per_layer']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better',
                                          'source', 'layer', 'moves'}
        assert m['source'] in SOURCES
        assert 1 <= len(m['layer']) <= 200
    for m in manifest['end_to_end'] + manifest['per_layer']:
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['better'] in ('lower', 'higher')
    for group in ('configs', 'workloads'):
        names = [x['name'] for x in manifest[group]]
        assert len(names) == len(set(names))
    metrics = [m['name'] for m in
               manifest['end_to_end'] + manifest['per_layer']]
    assert len(metrics) == len(set(metrics))
    four = sum(c['chips'] == 4 for c in manifest['workloads'])
    assert four <= max(1, len(manifest['workloads']) // 4)


def test_every_cell_has_its_files_and_metrics(manifest):
    configs = {c['name']: c for c in manifest['configs']}
    used = set()
    pairs = set()
    for cell in manifest['workloads']:
        used.add(cell['config'])
        assert (cell['config'], cell['traffic']) not in pairs
        pairs.add((cell['config'], cell['traffic']))
        cfg_file = configs[cell['config']]['file']
        assert cfg_file.startswith('benchmark/configs/')
        cfg = json.load(open(os.path.join(REPO, cfg_file)))
        traffic = json.load(open(os.path.join(
            REPO, 'benchmark', 'traffic', cell['traffic'] + '.json')))
        importlib.import_module('benchmark.runners.' + cfg['runner'])
        importlib.import_module(
            'benchmark.generators.' + traffic['generator'])
        e2e = [m['name'] for m in manifest['end_to_end']
               if cell['name'] in cells_of(m, manifest)]
        assert 'setup_s' in e2e and len(e2e) >= 2
        assert any(cell['name'] in cells_of(m, manifest)
                   for m in manifest['per_layer'])
    assert used == set(configs)
    files = [c['file'] for c in manifest['configs']]
    assert len(files) == len(set(files))


def test_moves_names_a_metric_its_cells_report(manifest):
    e2e = {m['name']: m for m in manifest['end_to_end']}
    for m in manifest['per_layer']:
        assert m['moves'] in e2e, m
        for cell in cells_of(m, manifest):
            assert cell in cells_of(e2e[m['moves']], manifest), \
                f'{m["name"]} moves {m["moves"]}, not reported in {cell}'


def test_every_layer_metric_has_a_reader_and_is_declared_once(manifest):
    """BENCHMARK.json declares a per-layer metric; its own file names
    the reader and the reader's parameters, and repeats nothing."""
    layers = set()
    for m in manifest['per_layer']:
        spec = json.load(open(os.path.join(
            REPO, 'benchmark', 'layer_metrics', m['name'] + '.json')))
        assert set(spec) == {'reader', 'params'}, m['name']
        reader = importlib.import_module(
            'benchmark.readers.' + spec['reader'])
        assert callable(reader.read)
        layers.add(m['layer'])
    declared = {m['name'] + '.json' for m in manifest['per_layer']}
    assert set(os.listdir(os.path.join(
        REPO, 'benchmark', 'layer_metrics'))) == declared
    perf = open(os.path.join(REPO, 'PERF.md')).read()
    for layer in layers:
        assert f'**{layer}**' in perf, f'PERF.md does not list {layer}'


def test_train_mfu_divides_by_the_chips_of_the_cell():
    """The four-chip training cell is a data-only addition: its MFU is
    over four chips' peak, from the cell's own `chips`."""
    from benchmark.readers import train_mfu
    cfg = json.load(open(os.path.join(
        REPO, 'benchmark/configs/cerebras_gpt_1p3b_train.json')))
    ctx = {'counters': {'tokens_per_s': 34000.0}, 'on_tpu': True,
           'device_kind': 'TPU v5 lite', 'config': cfg,
           'traffic': {'seq_len': 2048}, 'chips': 1}
    one = train_mfu.read({}, ctx)
    assert 50 < one < 60
    assert train_mfu.read({}, dict(ctx, chips=4)) \
        == pytest.approx(one / 4)
    assert train_mfu.read({}, dict(ctx, on_tpu=False)) is None


def test_retention_step_mfu_is_the_decoders_operations_over_the_peak():
    """Beside the retention kernel's roofline stands the whole decode
    step's share of the chip's peak, moving the same metric: 7.7 GFLOP
    a served token (6.84 G of it the weights' two operations a
    parameter met) at the ledger's 555 tokens/s is 2.2% of 197 TFLOP/s,
    a step bound by bytes."""
    from benchmark import retention_flops
    from benchmark.readers import retention_step_mfu
    cfg = json.load(open(os.path.join(
        REPO, 'benchmark/configs/brumby_14b_serve.json')))
    per_token = retention_flops.decode_step_ops_per_token(cfg['model'])
    weights = 2 * (8 * 330.35e6 + 5120 * 151936)
    assert weights < per_token < 1.15 * weights
    ctx = {'counters': {'decoded_tokens': 555.5 * 45, 'window_ms': 45e3},
           'on_tpu': True, 'device_kind': 'TPU v5 lite', 'config': cfg,
           'chips': 1}
    assert 2.1 < retention_step_mfu.read({}, ctx) < 2.3
    assert retention_step_mfu.read({}, dict(ctx, on_tpu=False)) is None
    assert retention_step_mfu.read({}, dict(ctx, counters={})) is None


def test_configuration_files_say_what_they_stand_for(manifest):
    for c in manifest['configs']:
        cfg = json.load(open(os.path.join(REPO, c['file'])))
        for key in ('source', 'reduced', 'assumed', 'deployment',
                    'published', 'model', 'probe'):
            assert key in cfg, (c['name'], key)
        assert sorted(cfg['reduced']) == sorted(c['reduced'])
        pub, model = cfg['published'], cfg['model']
        # no width is cut
        assert model['hidden_size'] == pub['n_embd']
        assert model['num_heads'] == pub['n_head']
        assert model['intermediate_size'] == pub['n_inner']
        assert model['max_seq_len'] == pub['n_positions']
        assert model['published_vocab_size'] == pub['vocab_size']
        if 'num_layers' not in c['reduced']:
            assert model['num_layers'] == pub['n_layer']
    serve = json.load(open(os.path.join(
        REPO, 'benchmark/configs/cerebras_gpt_1p3b_serve.json')))
    pool, m = serve['kv_pool'], serve['model']
    assert pool['num_blocks'] == serve['serve']['num_blocks']
    assert pool['num_blocks'] % 64 == 0
    per_pos = 2 * m['num_layers'] * m['hidden_size'] * 4
    assert pool['dtype'] == 'float32'
    assert pool['bytes_per_position'] == per_pos
    assert pool['positions'] == pool['num_blocks'] \
        * serve['serve']['block_size']
    assert pool['bytes'] == pool['positions'] * per_pos
    # "a pool that fills the rest of the chip": the weights (12 h^2 a
    # layer with n_inner 4 h, their biases and norms, the tied embedding
    # and the positions, two bytes each) and the pool are at least 70%
    # of the chip, and leave 1 GB of what the compiler may use
    h, layers = m['hidden_size'], m['num_layers']
    assert serve['weights_dtype'] == 'bfloat16'
    weights = 2 * (layers * (12 * h * h + 13 * h)
                   + (m['vocab_size'] + m['max_seq_len']) * h + 2 * h)
    assert 2.62e9 < weights < 2.64e9
    hbm = json.load(open(os.path.join(
        REPO, 'benchmark/peaks.json')))['TPU v5 lite']['hbm_bytes']
    assert weights + pool['bytes'] >= 0.70 * hbm
    assert weights + pool['bytes'] <= 15.75 * 2 ** 30 - 1e9
