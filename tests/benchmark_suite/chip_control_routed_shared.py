#!/usr/bin/env python3
"""The checks of `serve_backlog_moe_shared_decode`'s `correct` that the
benchmark's own runs never make, on the chip at the cell's own size,
in ONE process (one engine, its modules compiled once; each seed's
weights are drawn and loaded into it anew).  It is
`chip_control_routed.py` with the cell, its runner
(`serve_routed_shared`) and its faults (`afmoe_faults.py`) in the
other's places:

    python3 tests/benchmark_suite/chip_control_routed_shared.py \\
        --seeds 1,2,3 --control-seeds 1 --tap-seeds 4,5 --fault-seed 1 \\
        --faults relu_for_silu,no_attention_gate --seconds 12

One JSON line each.  `probe`: the runner's own probe (logit gap, and
the direct limits on what the decode module handed out: two gated
attention outputs, a routed-plus-shared output, the expert flips) on
every seed: has to pass.  `control`: one engine serves the cell's
traffic for `seconds`; the runner's own `served` decides on the tokens
the program served (has to pass) and on the tokens the reference
itself puts first with its matrices rounded to float8_e4m3fn, the
nearest precision below the configuration's bfloat16; then the
runner's own `tap` decides on the program (`program_tap`: has to pass)
and on the reference in float8 standing in the program's place
(`control_tap`).  One of the two controls has to come out false
(`probe.why` in the configuration says which does, and by how much).
`--tap-seeds`: the two taps alone, without the served window.
`fault`: each of `--faults` (default: every one of
afmoe_faults.FAULTS) planted in the program, the engine's modules
traced anew, the probe over the shortest and the longest bucket (to
compile two prefill modules a fault, not four): has to come out false.
"""
import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

CONTROL_DTYPE = 'float8_e4m3fn'


def say(msg):
    print(f'[control] {msg}', file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--seeds', default='')
    ap.add_argument('--control-seeds', default='')
    ap.add_argument('--tap-seeds', default='')
    ap.add_argument('--fault-seed', default='')
    ap.add_argument('--faults', default='')
    ap.add_argument('--seconds', type=float, default=12.0)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('chip_control_routed_shared: no TPU, no reading')
    from paddle_tpu.core import compile_cache
    compile_cache.setup_xla_cache()
    from benchmark import harness, logit_gap
    from benchmark.runners import serve
    from benchmark.runners import serve_routed_shared as runner
    import afmoe_faults
    cell = harness.load_cell('serve_backlog_moe_shared_decode')
    config, traffic = cell['config'], cell['traffic']
    seeds = [int(s) for s in args.seeds.split(',') if s]
    controls = [int(s) for s in args.control_seeds.split(',') if s]
    taps = [int(s) for s in args.tap_seeds.split(',') if s]
    first = (seeds + controls + taps + [int(args.fault_seed or 0)])[0]
    t0 = time.monotonic()
    model, engine, weights = runner.build(config, first,
                                                time.monotonic)
    engine.warmup()
    say(f'engine and warm-up {time.monotonic() - t0:.1f}s')
    loaded = first

    def load(seed):
        nonlocal weights, loaded
        if seed != loaded:
            # let the last seed's tensors go as the new ones come: two
            # whole models do not fit the chip
            weights.clear()
            engine._params = engine._buffers = None
            weights = runner.load_weights(config, model, seed)
            engine._params, engine._buffers = model.functional_state()
            loaded = seed
        return runner.reference(config, weights)

    def out(kind, seed, ok, compared, **more):
        print(json.dumps({'kind': kind, 'seed': seed, 'ok': bool(ok),
                          'compared': compared, **more}), flush=True)

    def tap_both(seed):
        for name, lower in (('program_tap', None),
                            ('control_tap', CONTROL_DTYPE)):
            compared = {}
            ok = runner.tap(config, engine, weights, seed, say,
                                  compared, weights_as=lower)
            out(name, seed, ok, compared)

    for seed in seeds:
        logits_at = load(seed)
        compared = {}
        ok = runner.probe(config, engine, weights, logits_at, seed,
                                say, compared)
        out('probe', seed, ok, compared)

    for seed in controls:
        logits_at = load(seed)
        requests = importlib.import_module(
            'benchmark.generators.' + traffic['generator']).make(
                traffic, seed, args.seconds)
        engine.run(requests, timeout_s=args.seconds)
        rows = logit_gap.sample(requests, seed,
                                config['probe']['served_requests'])
        lower = logit_gap.first_choices(
            runner.reference(config, weights,
                                   weights_as=CONTROL_DTYPE), rows,
            int(traffic['prompt_len']['hi'] + traffic['new_tokens']['hi']),
            int(config['probe']['served_tokens']))
        for name, judged in (('program', None), ('control', lower)):
            compared = {}
            ok = serve.served(config, traffic, rows, logits_at, say,
                              compared, judged=judged)
            out(name, seed, ok, compared)
        tap_both(seed)

    for seed in taps:
        load(seed)
        tap_both(seed)

    if args.fault_seed:
        seed = int(args.fault_seed)
        logits_at = load(seed)
        buckets = (min(engine.config.prompt_buckets),
                   max(engine.config.prompt_buckets))
        for fault in (args.faults.split(',') if args.faults
                      else afmoe_faults.FAULTS):
            t0 = time.monotonic()
            restore = afmoe_faults.plant(fault)
            engine._modules.clear()
            try:
                compared = {}
                ok = runner.probe(config, engine, weights,
                                        logits_at, seed, say, compared,
                                        buckets=buckets)
            finally:
                restore()
                engine._modules.clear()
            out('fault', seed, ok, compared, fault=fault,
                seconds=round(time.monotonic() - t0, 1))


if __name__ == '__main__':
    main()
