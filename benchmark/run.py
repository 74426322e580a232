#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result (see benchmark/README.md); the
lines before it on stderr say where set-up went and how jax's compile
cache fared.  Refuses to run without a TPU.
"""
import time
T_PROCESS_START = time.monotonic()
MARKS = [('start', T_PROCESS_START, time.process_time())]

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def say(msg):
    print(f'[bench] {msg}', file=sys.stderr, flush=True)


def mark(name):
    """A phase of start-up ends: its name, the clock, the process's
    own CPU time (a phase that waited shows more clock than CPU)."""
    MARKS.append((name, time.monotonic(), time.process_time()))


def run_cell(cell, seed, seconds, trace_on, t_start, chip_open_s=None,
             **runner_kwargs):
    """Run a loaded cell through its configuration's runner and return
    the result line as a dict.  `chip_open_s`, the wait for the TPU
    runtime to open the device, is no part of `setup_s`: it is the
    per-layer metric `chip_open_s` beside it (PERF.md, section 2)."""
    from benchmark import harness
    runner = importlib.import_module(
        'benchmark.runners.' + cell['config']['runner'])
    run = runner.run(cell, int(seed), float(seconds), bool(trace_on),
                     t_start + (chip_open_s or 0.0), say=say,
                     **runner_kwargs)
    if chip_open_s is not None:
        run['counters']['chip_open_s'] = chip_open_s
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
    mark('harness')
    import jax
    mark('import jax')
    if jax.default_backend() != 'tpu':
        sys.exit(f'benchmark: jax.default_backend() is '
                 f'{jax.default_backend()!r}, not tpu: no accelerator, '
                 'no result')
    if len(jax.devices()) < cell['chips']:
        sys.exit(f'benchmark: {args.workload} needs {cell["chips"]} '
                 f'chips, jax finds {len(jax.devices())}')
    mark('open the chip')
    # the one call above that waits for the TPU runtime: 7 to 10 s that
    # differ from run to run by more than all else in set-up together,
    # spent inside libtpu with the device's driver open (PR 30)
    chip_open_s = MARKS[-1][1] - MARKS[-2][1]
    from paddle_tpu.core import compile_cache
    mark('import paddle_tpu')
    say(f'jax compile cache at {compile_cache.setup_xla_cache()}')
    say('start-up: ' + ', '.join(
        f'{name} {t - t0:.2f}s (cpu {c - c0:.2f})'
        for (_, t0, c0), (name, t, c) in zip(MARKS, MARKS[1:])))
    line = run_cell(cell, args.seed, args.seconds, args.trace,
                    T_PROCESS_START, chip_open_s=chip_open_s)
    for name, (value, limit) in line.get('compared', {}).items():
        say(f'compared: {name} {value:.6g}, limit {limit:.6g}')
    print(json.dumps(line), flush=True)


if __name__ == '__main__':
    main()
