#!/usr/bin/env python
"""Scan-over-layers decode A/B (GPTConfig.scan_decode_blocks).

Scanning one block body over stacked per-layer params shrinks the
decode module ~num_layers-fold.  CPU measured compile -28% but
runtime +71% (models/gpt.py GPTConfig comment) — this A/B decides
whether the TPU compile shrink is worth the TPU runtime delta.
Token-exact parity between the two forms is locked in
tests/test_kv_cache.py.

Prints one JSON line with per-arm warmup (trace+compile+first run)
seconds and decoded tok/s.  Kept-or-killed: scan becomes the decode
default only if tok/s holds within ~5% AND compile drops materially.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# UNLIKE the other chip tools, this one must NOT use the persistent
# XLA cache: compile time IS the decision metric, and a warm cache
# would collapse both arms' warmup_s to cache-load time.  A tool that
# wants cold compiles turns the cache off; it does not move it.
os.environ['PADDLE_TPU_COMPILE_CACHE'] = '0'


def bench(scan, args):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_small, gpt_tiny

    paddle.seed(0)
    if args.smoke:
        model, batch, prompt, new = (
            gpt_tiny(scan_decode_blocks=scan), 2, 8, 8)
    else:
        model = gpt_small(max_seq_len=args.prompt + args.new,
                          dropout=0.0, scan_decode_blocks=scan)
        batch, prompt, new = args.batch, args.prompt, args.new
    model.eval()
    rs = np.random.RandomState(0)
    ids = rs.randint(0, model.config.vocab_size,
                     size=(batch, prompt)).astype('int64')
    t0 = time.time()
    out = model.generate(paddle.to_tensor(ids), max_new_tokens=new,
                         temperature=0)
    np.asarray(out.value)
    warmup_s = time.time() - t0
    print(f'{"scan" if scan else "unrolled"} warmup '
          f'(trace+compile+run): {warmup_s:.1f}s', file=sys.stderr)
    t0 = time.time()
    for i in range(args.iters):
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=new,
                             temperature=0, seed=i)
        np.asarray(out.value)     # completion barrier: host readback
    dt = time.time() - t0
    return {'warmup_s': round(warmup_s, 1),
            'tokens_per_s': batch * new * args.iters / dt}


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
    setup_xla_cache()       # applies the switch-off above
    print(f'device: {jax.devices()[0]}', file=sys.stderr)
    rows = {}
    for scan in (True, False):
        name = 'scan' if scan else 'unrolled'
        rows[name] = r = bench(scan, args)
        print(f"{name}: {r['tokens_per_s']:.0f} tok/s "
              f"(warmup {r['warmup_s']}s)", file=sys.stderr)
    rows['speedup_scan_over_unrolled'] = (
        rows['scan']['tokens_per_s'] / rows['unrolled']['tokens_per_s'])
    rows['compile_ratio'] = (rows['scan']['warmup_s'] /
                             max(rows['unrolled']['warmup_s'], 1e-9))
    print(json.dumps(rows))


if __name__ == '__main__':
    main()
