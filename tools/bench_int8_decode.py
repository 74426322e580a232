#!/usr/bin/env python
"""Decode-level int8 A/B (SURVEY §2 item 72 follow-through): GPT-2
small KV-cache generation with bf16 vs int8 (quantize_dynamic_int8)
projections.  The decode step is weight-bandwidth-bound, so int8
weights (half of bf16 in HBM) should raise decoded tokens/s if the
op-level win (tools/bench_int8_matmul.py) carries into the full
module.  Kept-or-killed: int8 decode becomes a documented serving
default only if this wins on chip.

Prints one JSON line {bf16: tok/s, int8: tok/s, speedup}.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench(use_int8, args):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_small, gpt_tiny
    from paddle_tpu.quantization import quantize_dynamic_int8

    paddle.seed(0)
    if args.smoke:
        model, batch, prompt, new = gpt_tiny(), 2, 8, 8
    else:
        model = gpt_small(max_seq_len=args.prompt + args.new,
                          dropout=0.0)
        batch, prompt, new = args.batch, args.prompt, args.new
    model.eval()
    if use_int8:
        quantize_dynamic_int8(model)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, model.config.vocab_size,
                     size=(batch, prompt)).astype('int64')
    t0 = time.time()
    out = model.generate(paddle.to_tensor(ids), max_new_tokens=new,
                         temperature=0)
    np.asarray(out.value)
    print(f'{"int8" if use_int8 else "bf16"} warmup (incl. compile): '
          f'{time.time() - t0:.1f}s', file=sys.stderr)
    t0 = time.time()
    for i in range(args.iters):
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=new,
                             temperature=0, seed=i)
        np.asarray(out.value)     # completion barrier: host readback
    dt = time.time() - t0
    return batch * new * args.iters / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--smoke', action='store_true')
    ap.add_argument('--iters', type=int, default=5)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--prompt', type=int, default=128)
    ap.add_argument('--new', type=int, default=128)
    args = ap.parse_args()
    if args.smoke:
        args.iters = 2

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    print(f'device: {jax.devices()[0]}', file=sys.stderr)
    rows = {}
    for use_int8 in (False, True):
        name = 'int8' if use_int8 else 'bf16'
        rows[name] = v = bench(use_int8, args)
        print(f'{name}: {v:.0f} decoded tok/s', file=sys.stderr)
    rows['speedup_int8_over_bf16'] = rows['int8'] / rows['bf16']
    print(f"speedup: {rows['speedup_int8_over_bf16']:.3f}x",
          file=sys.stderr)
    print(json.dumps(rows))


if __name__ == '__main__':
    main()
