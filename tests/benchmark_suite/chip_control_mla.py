#!/usr/bin/env python3
"""The checks of `serve_backlog_mla_longdoc`'s `correct` that the
benchmark's own runs never make, on the chip at the cell's own size, in
ONE process (one engine, its modules compiled once; each seed's weights
are drawn and loaded into it anew).  It is
`chip_control_routed_shared.py` with the cell, its runner (`serve_mla`)
and its faults (`mla_faults.py`) in the other's places:

    python3 tests/benchmark_suite/chip_control_mla.py \\
        --seeds 1,2 --control-seeds 3 --tap-seeds 4 --fault-seed 1 \\
        --faults w_uk_transposed,kv_norm_skipped

One JSON line each.  `probe`: the runner's own probe (logit gap, the
share of tokens under the reference's best, and the direct limits on
what the decode module handed out: two latent attention outputs, a
routed-plus-shared output, the expert flips) on every seed: has to
pass.  `--control-seeds`: the probe's prompts, one a bucket, served
together as the probe serves them; then `logit_gap.check` holds to the
float32 reference the tokens the program chose (`program`: has to
pass) and the tokens the reference itself puts first with its matrices
rounded to float8_e4m3fn at the same positions of the same rows
(`control`), each with its share of tokens under the reference's best
(`not_best`): the two readings the probe's gap and share limits lie
between.  `--tap-seeds`: the runner's own `tap` on the program
(`program_tap`: has to pass) and on the reference with its matrices
rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16, standing in the program's place
(`control_tap`: has to fail).  `fault`: each of `--faults` (default:
every one of mla_faults.FAULTS) planted in the program, the engine's
modules traced anew, the probe over the shortest and the longest
bucket: has to come out false.
"""
import argparse
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
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('chip_control_mla: no TPU, no reading')
    from paddle_tpu.core import compile_cache
    compile_cache.setup_xla_cache()
    import numpy as np
    from benchmark import harness, logit_gap
    from benchmark.runners import serve_mla as runner
    from paddle_tpu.serving.scheduler import Request
    from benchmark.runners import serve_routed_shared as shared
    import mla_faults
    config = harness.load_cell('serve_backlog_mla_longdoc')['config']
    seeds = [int(s) for s in args.seeds.split(',') if s]
    controls = [int(s) for s in args.control_seeds.split(',') if s]
    taps = [int(s) for s in args.tap_seeds.split(',') if s]
    first = (seeds + controls + taps + [int(args.fault_seed or 0)])[0]
    t0 = time.monotonic()
    model, engine, weights = runner.build(config, first, time.monotonic)
    engine.warmup()
    say(f'engine and warm-up {time.monotonic() - t0:.1f}s')
    loaded = first

    def load(seed):
        nonlocal weights, loaded
        if seed != loaded:
            # let the last seed's tensors go as the new ones come: two
            # whole models do not fit beside the pool
            weights.clear()
            engine._params = engine._buffers = None
            weights = runner.load_weights(config, model, seed)
            engine._params, engine._buffers = model.functional_state()
            loaded = seed
        return runner.reference(config, weights)

    def out(kind, seed, ok, compared, **more):
        print(json.dumps({'kind': kind, 'seed': seed, 'ok': bool(ok),
                          'compared': compared, **more}), flush=True)

    for seed in seeds:
        logits_at = load(seed)
        compared = {}
        ok = shared.probe(config, engine, weights, logits_at, seed, say,
                          compared, tap=runner.tap)
        out('probe', seed, ok, compared)

    p = config['probe']
    for seed in controls:
        logits_at = load(seed)
        rng = np.random.default_rng([seed, 2])
        reqs = [Request(f'control{b}', rng.integers(
                    0, int(config['model']['published_vocab_size']),
                    size=int(b) - 5, dtype=np.int64),
                    int(p['new_tokens']), arrival_t=0.0)
                for b in engine.config.prompt_buckets]
        engine.run(reqs)
        rows = [(r.prompt, list(r.tokens)) for r in reqs]
        width, keep = engine.config.max_model_len, int(p['new_tokens'])
        lower = logit_gap.first_choices(
            runner.reference(config, weights, weights_as=CONTROL_DTYPE),
            rows, width, keep, block=1)
        for name, judged in (('program', None), ('control', lower)):
            compared = {}
            ok, gaps = logit_gap.check(
                'probe_logit_gap', logits_at, rows, p['logit_gap_tol'],
                say, compared, width=width, keep=keep, block=1,
                judged=judged,
                id_limit=int(config['model']['published_vocab_size']))
            out(name, seed, ok, compared,
                not_best=float((gaps > 0).mean()))

    for seed in taps:
        load(seed)
        for name, lower in (('program_tap', None),
                            ('control_tap', CONTROL_DTYPE)):
            compared = {}
            ok = runner.tap(config, engine, weights, seed, say, compared,
                            weights_as=lower)
            out(name, seed, ok, compared)

    if args.fault_seed:
        seed = int(args.fault_seed)
        logits_at = load(seed)
        buckets = (min(engine.config.prompt_buckets),
                   max(engine.config.prompt_buckets))
        for fault in (args.faults.split(',') if args.faults
                      else mla_faults.FAULTS):
            t0 = time.monotonic()
            restore = mla_faults.plant(fault)
            engine._modules.clear()
            try:
                compared = {}
                ok = shared.probe(config, engine, weights, logits_at, seed,
                                  say, compared, buckets=buckets,
                                  tap=runner.tap)
            finally:
                restore()
                engine._modules.clear()
            out('fault', seed, ok, compared, fault=fault,
                seconds=round(time.monotonic() - t0, 1))


if __name__ == '__main__':
    main()
