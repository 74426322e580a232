"""Every examples/ script must actually run (tiny settings) — an
example that rots is worse than none."""
import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), '..', 'examples')


def run_example(name, *args, timeout=600):
    env = dict(os.environ)
    repo = os.path.abspath(os.path.join(EXAMPLES, '..'))
    env['PYTHONPATH'] = repo + os.pathsep + env.get('PYTHONPATH', '')
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, name), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.join(EXAMPLES, '..'))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


class TestExamples:
    def test_mnist_lenet(self):
        out = run_example('mnist_lenet.py', '--epochs', '1',
                          '--batch-size', '32', '--limit-steps', '3')
        assert 'eval:' in out

    def test_resnet_train(self):
        out = run_example('resnet_train.py', '--steps', '3',
                          '--batch-size', '8', '--depth', '18',
                          '--image', '32', '--classes', '10')
        assert 'imgs/s' in out

    def test_resnet_train_s2d(self):
        out = run_example('resnet_train.py', '--steps', '2',
                          '--batch-size', '4', '--depth', '18',
                          '--image', '32', '--classes', '10',
                          '--space-to-depth')
        assert 'imgs/s' in out

    def test_bert_pretrain(self):
        out = run_example('bert_pretrain.py', '--steps', '2',
                          '--batch-size', '4', '--seq-len', '32')
        assert out.count('mlm_loss=') == 2

    def test_gpt_train_generate(self):
        out = run_example('gpt_train_generate.py', '--train-steps', '2',
                          '--seq-len', '32', '--new-tokens', '4')
        assert 'decoded :' in out

    def test_gpt_int8(self):
        out = run_example('gpt_train_generate.py', '--train-steps', '1',
                          '--seq-len', '16', '--new-tokens', '4',
                          '--int8')
        assert 'Int8DynamicLinear' in out and 'decoded :' in out

    def test_distributed_hybrid(self):
        # conftest already forces the 8-device CPU mesh for children
        out = run_example('distributed_hybrid.py', '--dp', '2',
                          '--tp', '2', '--steps', '2')
        assert out.count('loss=') == 2

    def test_distributed_hybrid_zero2(self):
        out = run_example('distributed_hybrid.py', '--dp', '4',
                          '--tp', '1', '--steps', '2', '--zero', '2')
        assert out.count('loss=') == 2

    def test_static_graph(self):
        out = run_example('static_graph.py', '--steps', '100')
        lines = [ln for ln in out.splitlines() if 'final loss' in ln]
        assert lines and float(lines[0].split(':')[1]) < 0.1

    def test_readme_lists_every_script(self):
        with open(os.path.join(EXAMPLES, 'README.md')) as f:
            readme = f.read()
        scripts = [f for f in os.listdir(EXAMPLES)
                   if f.endswith('.py')]
        missing = [s for s in scripts if s not in readme]
        assert not missing, missing
