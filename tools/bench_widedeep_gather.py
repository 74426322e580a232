#!/usr/bin/env python
"""A/B the Wide&Deep fused single-table gather against reference-style
per-field tables (VERDICT r4 task 5, PERF round-3 lead 3).

Times a full Wide&Deep training step (criteo-like: 26 sparse fields of
100k rows, 13 dense, AMP) with `fused_gather` on/off on whatever
device jax sees, and prints examples/s for both plus the speedup.
Kept-or-killed verdict: the fused gather stays the default only if it
wins on chip.

Usage:
    python tools/bench_widedeep_gather.py [--smoke] [--iters 20]
        [--batch 16384]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench(fused, args):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models.widedeep import WideDeep
    from paddle_tpu.parallel import ParallelTrainer
    from paddle_tpu.distributed import fleet, env as dist_env

    paddle.seed(0)
    if args.smoke:
        batch, fields, dense_dim, hidden = 256, [1000] * 4, 4, (32,)
    else:
        batch, fields, dense_dim, hidden = (args.batch, [100_000] * 26,
                                            13, (400, 400, 400))
    model = WideDeep(fields, dense_dim=dense_dim, embed_dim=16,
                     hidden=hidden, fused_gather=fused)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    bce = nn.BCEWithLogitsLoss()
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs['use_pure_fp16'] = True
    trainer = ParallelTrainer(model, opt, lambda o, y: bce(o, y),
                              n_inputs=2, strategy=strategy)
    rs = np.random.RandomState(0)
    ids = jax.device_put(np.stack(
        [rs.randint(0, f, size=batch) for f in fields],
        axis=1).astype('int64'))
    dense = jax.device_put(rs.rand(batch, dense_dim).astype('float32'))
    y = jax.device_put(
        rs.randint(0, 2, size=(batch, 1)).astype('float32'))
    loss = None
    for _ in range(args.warmup):
        loss = trainer.step(ids, dense, y)
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(args.iters):
        loss = trainer.step(ids, dense, y)
    jax.block_until_ready(loss)
    # readback inside the timed region: a barrier that cannot be
    # skipped; inflates both arms equally, the ratio is the number
    # to trust
    float(np.asarray(loss).ravel()[0])
    dt = time.time() - t0
    dist_env.set_mesh(None)
    return {'examples_per_s': batch * args.iters / dt,
            'ms_per_step': dt / args.iters * 1e3,
            'loss': float(np.asarray(loss).ravel()[0])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--smoke', action='store_true')
    ap.add_argument('--iters', type=int, default=20)
    ap.add_argument('--warmup', type=int, default=4)
    ap.add_argument('--batch', type=int, default=16384)
    args = ap.parse_args()
    if args.smoke:
        args.iters, args.warmup = 3, 2

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    print(f'device: {jax.devices()[0]}', file=sys.stderr)
    rows = {}
    for fused in (True, False):
        name = 'fused' if fused else 'per_field'
        rows[name] = r = bench(fused, args)
        print(f"{name}: {r['examples_per_s']:.0f} ex/s "
              f"({r['ms_per_step']:.1f} ms) loss={r['loss']:.4f}",
              file=sys.stderr)
    rows['speedup_fused_over_per_field'] = (
        rows['fused']['examples_per_s'] /
        rows['per_field']['examples_per_s'])
    print(f"speedup: {rows['speedup_fused_over_per_field']:.3f}x",
          file=sys.stderr)
    print(json.dumps(rows))


if __name__ == '__main__':
    main()
