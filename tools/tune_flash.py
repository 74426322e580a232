#!/usr/bin/env python
"""Flash-attention block sweep: forward plus backward per (block_q,
block_k), in the operands' dtype, printed for every candidate.

Run ON THE REAL CHIP.  The table flash_attention() consults is the
literal `_tune_table` in paddle_tpu/ops/flash_attention.py: a builder
who wants an entry pastes it there with the sweep's readings in PERF.md.
The default shape is the one the benchmark measures (train_seq2048:
[4 x 16 heads, 2048, 128] bfloat16, causal).

    python tools/tune_flash.py
    python tools/tune_flash.py --tq 1024 --d 64 --bh 96
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--tq', type=int, default=2048)
    ap.add_argument('--tk', type=int, default=None)
    ap.add_argument('--d', type=int, default=128)
    ap.add_argument('--bh', type=int, default=64)
    ap.add_argument('--no-causal', action='store_true')
    args = ap.parse_args()

    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    from paddle_tpu.ops.flash_attention import (
        _tuned_blocks, autotune_blocks)

    tq, tk, d = args.tq, args.tk or args.tq, args.d
    causal = not args.no_causal
    now = _tuned_blocks(tq, tk, d, causal)
    print(f'T={tq}x{tk} d={d} bh={args.bh} causal={causal}: forward + '
          f'backward, ms a call; the module resolves {now}', flush=True)
    best, ms = autotune_blocks(
        tq, tk, d, causal=causal, bh=args.bh,
        report=lambda blocks, ms: print(f'  blocks={blocks}: {ms:.3f} ms',
                                        flush=True))
    print(f'best blocks={best} ({ms:.3f} ms)', flush=True)


if __name__ == '__main__':
    main()
