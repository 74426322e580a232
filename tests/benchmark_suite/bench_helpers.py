"""Shared by the benchmark's tests: cells loaded from BENCHMARK.json
with the tiny configuration and traffic files of this directory put in
place of the real ones, handed to the runner's function directly."""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = {
    'train_seq2048': ('tiny_train', 'tiny_steps'),
    'serve_backlog': ('tiny_serve', 'tiny_backlog'),
    'serve_chat_steady': ('tiny_serve', 'tiny_chat'),
}


def tiny_cell(workload, config=None, traffic=None):
    from benchmark import harness
    cell = harness.load_cell(workload)
    cfg, mix = TINY[workload]
    cell['config'] = harness.load_json(os.path.join(
        HERE, 'configs', (config or cfg) + '.json'))
    cell['traffic'] = harness.load_json(os.path.join(
        HERE, 'traffic', (traffic or mix) + '.json'))
    return cell


def run_tiny(workload, seed=2147495993, seconds=1.0, trace=0,
             config=None, **runner_kwargs):
    from benchmark import run
    return run.run_cell(tiny_cell(workload, config=config), seed, seconds,
                        trace, time.monotonic(), **runner_kwargs)


class JumpyClock:
    """A clock that jumps ahead by `jump_s` on every `every`-th
    reading: the engine sees a stall between (and inside)
    interventions, over and over, from the probe to the drain."""

    def __init__(self, every=48, jump_s=0.2):
        self.every, self.jump_s = every, jump_s
        self.reads = 0
        self.ahead = 0.0

    def __call__(self):
        self.reads += 1
        if self.reads % self.every == 0:
            self.ahead += self.jump_s
        return time.monotonic() + self.ahead
