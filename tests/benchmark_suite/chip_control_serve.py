#!/usr/bin/env python3
"""The control of the GPT serve cells' `correct`, on the chip at a
cell's own size (and, from test_benchmark_serve.py, at a tiny one):

    python3 tests/benchmark_suite/chip_control_serve.py --workload serve_backlog --seeds 1,2,3 --seconds 12

For each seed one engine serves the cell's own traffic for `seconds`
(with the drain the mix asks for) and the runner's own sample of what
the window finished is taken.  The runner's own comparison,
`serve.served`, then decides twice on those rows, one JSON line a
seed: on the tokens the program served (`program`, has to pass), and on
the tokens that the reference itself puts first at the same positions
with its matrices rounded to float8_e4m3fn, the nearest precision below
the configuration's bfloat16, in the program's place (`control`, has to
come out false with its `served_logit_gap` over the limit).  The limit
`probe.logit_gap_tol` lies between the two readings.  The benchmark's
own runs never run this.  Several seeds run as one child process each.
"""
import argparse
import importlib
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CONTROL_DTYPE = 'float8_e4m3fn'


def readings(cell, seed, seconds, say=lambda msg: None):
    """What `serve.served` decides for one seed of one cell, on what the
    program served and on the control's choices in its place:
    {'program': (ok, worst gap), 'control': (ok, worst gap), 'tokens',
    'limit'}."""
    from benchmark import logit_gap
    from benchmark.runners import serve
    config, traffic = cell['config'], cell['traffic']
    _model, engine, weights = serve.build(config, seed, time.monotonic)
    engine.warmup()
    requests = importlib.import_module(
        'benchmark.generators.' + traffic['generator']).make(
            traffic, seed, seconds)
    engine.run(requests, timeout_s=seconds + float(traffic['drain_s']))
    rows = logit_gap.sample(requests, seed,
                            config['probe']['served_requests'])
    logits_at = serve.reference(config, weights)
    lower = logit_gap.first_choices(
        serve.reference(config, weights, weights_as=CONTROL_DTYPE), rows,
        int(traffic['prompt_len']['hi'] + traffic['new_tokens']['hi']),
        int(traffic['new_tokens']['hi']))
    out = {'tokens': sum(len(t) for _, t in rows)}
    for name, judged in (('program', None), ('control', lower)):
        compared = {}
        ok = serve.served(config, traffic, rows, logits_at, say, compared,
                          judged=judged)
        gap, out['limit'] = compared['served_logit_gap']
        out[name] = (ok, gap)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, default=12.0)
    args = ap.parse_args(argv)
    seeds = args.seeds.split(',')
    if len(seeds) > 1:
        # a process a seed, one after another: an engine's pool is not
        # given back while its process lives, and this parent stays
        # off jax, so that each child finds the chip free
        for seed in seeds:
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            '--workload', args.workload, '--seeds', seed,
                            '--seconds', str(args.seconds)], check=True)
        return
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('chip_control_serve: no TPU, no readings')
    from benchmark import harness
    from paddle_tpu.core import compile_cache
    compile_cache.setup_xla_cache()
    cell = harness.load_cell(args.workload)
    read = readings(cell, int(seeds[0]), args.seconds,
                    say=lambda msg: print(f'[control] {msg}',
                                          file=sys.stderr, flush=True))
    print(json.dumps({
        'workload': args.workload, 'seed': int(seeds[0]),
        'tokens': read['tokens'], 'limit': read['limit'],
        'program': read['program'][1], 'control': read['control'][1],
        'program_correct': read['program'][0],
        'control_correct': read['control'][0]}), flush=True)


if __name__ == '__main__':
    main()
