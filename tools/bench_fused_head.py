#!/usr/bin/env python
"""A/B the fused LM head (ops/fused_ce.py) against the unfused path.

Times a GPT-2-small training step with fused_head on/off on whatever
device jax sees (the chip on a TPU machine; --smoke for a
CPU sanity pass), and prints tokens/s + step ms + estimated MFU for
both.  This is the one-command measurement for VERDICT r3 task 2
(close the transformer MFU gap): run it on the chip, paste the table
into PERF.md.

Usage:
    python tools/bench_fused_head.py [--smoke] [--iters 15]
        [--batch 8] [--seq 1024] [--chunks 8]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench(fused, args):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_small, gpt_tiny
    from paddle_tpu.parallel import ParallelTrainer
    from paddle_tpu.distributed import fleet, env as dist_env

    paddle.seed(0)
    if args.smoke:
        model = gpt_tiny(fused_head=fused,
                         fused_head_chunks=args.chunks)
        batch, seq = 2, 128
    else:
        model = gpt_small(max_seq_len=args.seq, dropout=0.0,
                          fused_head=fused,
                          fused_head_chunks=args.chunks)
        batch, seq = args.batch, args.seq
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs['use_pure_fp16'] = True
    trainer = ParallelTrainer(model, opt,
                              lambda out, y: model.loss(out, y),
                              strategy=strategy)
    rs = np.random.RandomState(0)
    V = model.config.vocab_size
    ids = jax.device_put(
        rs.randint(0, V, size=(batch, seq)).astype('int64'))
    loss = None
    for _ in range(args.warmup):
        loss = trainer.step(ids, ids)
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(args.iters):
        loss = trainer.step(ids, ids)
    jax.block_until_ready(loss)
    # the readback stays INSIDE the timed region on purpose: the
    # float() is a completion barrier that cannot be skipped.  Its
    # constant cost inflates both arms equally — the fused/unfused
    # RATIO is the number to trust; absolute tok/s carries the offset.
    float(np.asarray(loss).ravel()[0])
    dt = time.time() - t0
    toks = batch * seq * args.iters / dt
    # 6 * params * tokens FLOPs estimate (fwd+bwd), v5e peak 197 TF/s
    n_params = sum(
        int(np.prod(p.shape)) for p in model.parameters())
    flops = 6.0 * n_params * batch * seq / (dt / args.iters)
    mfu = flops / 197e12
    dist_env.set_mesh(None)
    return {'tokens_per_s': toks, 'ms_per_step': dt / args.iters * 1e3,
            'mfu_est': mfu, 'loss': float(np.asarray(loss).ravel()[0])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--smoke', action='store_true')
    ap.add_argument('--iters', type=int, default=15)
    ap.add_argument('--warmup', type=int, default=3)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq', type=int, default=1024)
    ap.add_argument('--chunks', type=int, default=8)
    ap.add_argument('--arm', choices=['both', 'fused', 'unfused'],
                    default='both',
                    help='chunk sweeps only need the fused arm — the '
                         'unfused baseline does not depend on --chunks')
    args = ap.parse_args()
    if args.smoke:
        args.iters, args.warmup = 3, 2

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    print(f'device: {jax.devices()[0]}', file=sys.stderr)
    rows = {}
    arms = {'both': (False, True), 'fused': (True,),
            'unfused': (False,)}[args.arm]
    for fused in arms:
        name = 'fused' if fused else 'unfused'
        rows[name] = r = bench(fused, args)
        print(f"{name}: {r['tokens_per_s']:.0f} tok/s "
              f"({r['ms_per_step']:.1f} ms, MFU~{r['mfu_est']:.1%}) "
              f"loss={r['loss']:.4f}", file=sys.stderr)
    if len(rows) == 2:
        print(f"speedup: {rows['fused']['tokens_per_s'] / rows['unfused']['tokens_per_s']:.3f}x",
              file=sys.stderr)
    import json
    print(json.dumps(rows))


if __name__ == '__main__':
    main()
