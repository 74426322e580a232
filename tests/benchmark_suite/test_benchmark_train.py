"""The train runner end to end at a tiny size on the CPU, the command's
refusal to run without a chip, and the compile cache across
processes."""
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import HERE, REPO, run_tiny


def test_train_result_line():
    line = run_tiny('train_seq2048', seconds=1.0)
    json.dumps(line)
    assert set(line) == {'correct', 'attempted', 'failed', 'metrics',
                         'device', 'compared'}
    assert list(line)[-1] == 'compared'
    assert set(line['compared']) == {
        'first_loss_rel', 'warmup_loss_not_falling', 'non_finite_losses'}
    assert all(v <= limit for v, limit in line['compared'].values())
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] >= 1
    assert set(line['metrics']) == {'train_tokens_per_s', 'setup_s'}
    assert line['metrics']['train_tokens_per_s']['unit'] == 'tokens/s'


def test_train_traced_reports_counters_and_no_device_number():
    line = run_tiny('train_seq2048', seconds=1.0, trace=1)
    assert set(line) == {'correct', 'attempted', 'failed', 'metrics',
                         'device', 'compared'}
    assert line['correct'] is True
    assert {'train_step_ms', 'compiles_in_window.train'} \
        <= set(line['metrics'])
    assert line['metrics']['compiles_in_window.train']['value'] == 0
    assert not {'train_mfu', 'device_idle.train', 'flash_fwd_roofline',
                'peak_hbm_gb.train'} & set(line['metrics'])


@pytest.mark.parametrize('trace', [0, 1])
def test_the_wait_for_the_chip_is_a_metric_of_its_own(trace):
    """`setup_s` leaves out the runtime's opening of the device, which
    a traced run reports beside it as `chip_open_s`."""
    import time
    from benchmark import run
    from bench_helpers import tiny_cell
    line = run.run_cell(tiny_cell('train_seq2048'), 2147495993, 0.3,
                        trace, time.monotonic() - 100.0, chip_open_s=60.0)
    if trace:
        assert line['metrics']['chip_open_s'] == {'value': 60.0,
                                                  'unit': 's'}
    else:
        assert 40.0 < line['metrics']['setup_s']['value'] < 100.0


def test_a_wrong_reference_turns_correct_false():
    line = run_tiny('train_seq2048', seconds=0.3, reference_perturb=0.05)
    assert line['correct'] is False and line['failed'] == 0


def test_train_takes_a_mesh_from_its_configuration():
    """PERF.md's open question 1 (dp2 x tp2 on four chips) is a new
    configuration file and one entry, no code."""
    import jax
    assert len(jax.devices()) >= 4
    line = run_tiny('train_seq2048', seconds=0.5, trace=1,
                    config='tiny_train_mesh')
    assert line['correct'] is True and line['attempted'] >= 1
    # traced, so every reader ran (train_mfu among them, which takes
    # the cell's chips and reports nothing off a chip)
    assert 'train_step_ms' in line['metrics']
    assert 'train_mfu' not in line['metrics']


def test_the_command_refuses_to_run_without_a_chip(capsys):
    from benchmark import run
    with pytest.raises(SystemExit) as stop:
        run.main(['--workload', 'train_seq2048', '--seed', '1',
                  '--seconds', '1', '--trace', '0'])
    assert stop.value.code not in (0, None)
    assert capsys.readouterr().out == ''


CHILD = r'''
import json, sys, time
sys.path.insert(0, {here!r})
from bench_helpers import run_tiny
from paddle_tpu.core import compile_cache
from benchmark import harness
compile_cache.setup_xla_cache()
count = harness.CompileCounter()
seed = int(sys.argv[1])
ok = [run_tiny(w, seed=seed, seconds=0.3)['correct']
      for w in ('train_seq2048', 'serve_chat_steady')]
print(json.dumps({{'ok': ok, 'hits': count.hits, 'misses': count.misses}}))
'''


def test_another_seed_in_a_second_process_misses_no_cached_program(
        tmp_path):
    """The seed enters no compiled program: a second process with
    another seed finds every program in jax's persistent cache."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS='cpu')
    env.pop('PADDLE_TPU_COMPILE_CACHE', None)
    env.pop('XLA_FLAGS', None)
    out = []
    for seed in (1, 2 ** 31 + 77):
        proc = subprocess.run(
            [sys.executable, '-c', CHILD.format(here=HERE), str(seed)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert out[0]['ok'] == out[1]['ok'] == [True, True]
    assert out[0]['misses'] > 0
    assert out[1]['misses'] == 0 and out[1]['hits'] >= out[0]['misses']
