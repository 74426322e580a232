#!/usr/bin/env python
"""Flash-attention block autotune sweep (PERF.md round-3 lead 4).

Run ON THE REAL CHIP; writes winners into
paddle_tpu/ops/flash_attention_tuning.json, which flash_attention()
consults per shape at call time.

    python tools/tune_flash.py                  # standard shape sweep
    python tools/tune_flash.py --tq 4096 --d 128
"""
import argparse
import sys

import os

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--tq', type=int, default=None)
    ap.add_argument('--tk', type=int, default=None)
    ap.add_argument('--d', type=int, default=None)
    ap.add_argument('--bh', type=int, default=8)
    ap.add_argument('--no-causal', action='store_true')
    args = ap.parse_args()

    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    from paddle_tpu.ops.flash_attention import autotune_blocks

    if args.tq:
        shapes = [(args.tq, args.tk or args.tq, args.d or 128)]
    else:
        # the bench/model shapes: GPT-2 small T=1024 d=64, BERT s128
        # (too small for pallas — skipped by the gate), longctx bench
        # = GPT-2 small at T=4096 so d stays 64, long-ctx 4096/8192
        # at d=128 for the larger-model face
        shapes = [(1024, 1024, 64), (2048, 2048, 64), (4096, 4096, 64),
                  (2048, 2048, 128), (4096, 4096, 128),
                  (8192, 8192, 128)]
    causal = not args.no_causal
    for tq, tk, d in shapes:
        best, ms = autotune_blocks(tq, tk, d, causal=causal, bh=args.bh)
        print(f'T={tq}x{tk} d={d} causal={causal}: best blocks={best} '
              f'({ms:.2f} ms/call)', flush=True)


if __name__ == '__main__':
    main()
