#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result (see benchmark/README.md); the
lines before it on stderr say where set-up went and how jax's compile
cache fared.  Refuses to run without a TPU.
"""
import time
T_PROCESS_START = time.monotonic()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def say(msg):
    print(f'[bench] {msg}', file=sys.stderr, flush=True)


def run_cell(cell, seed, seconds, trace_on, t_start, **runner_kwargs):
    """Run a loaded cell through its configuration's runner and return
    the result line as a dict."""
    from benchmark import harness
    runner = importlib.import_module(
        'benchmark.runners.' + cell['config']['runner'])
    run = runner.run(cell, int(seed), float(seconds), bool(trace_on),
                     t_start, say=say, **runner_kwargs)
    return harness.result_line(cell, run, bool(trace_on))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness
    cell = harness.load_cell(args.workload)
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit(f'benchmark: jax.default_backend() is '
                 f'{jax.default_backend()!r}, not tpu: no accelerator, '
                 'no result')
    if len(jax.devices()) < cell['chips']:
        sys.exit(f'benchmark: {args.workload} needs {cell["chips"]} '
                 f'chips, jax finds {len(jax.devices())}')
    from paddle_tpu.core import compile_cache
    say(f'jax compile cache at {compile_cache.setup_xla_cache()}')
    line = run_cell(cell, args.seed, args.seconds, args.trace,
                    T_PROCESS_START)
    print(json.dumps(line), flush=True)


if __name__ == '__main__':
    main()
