#!/usr/bin/env python
"""Chip A/B: bf16 matmul vs dynamic int8 matmul at GPT decode shapes.

Decode is weight-bandwidth-bound (every step streams all weights for a
[B, 1, H] activation), so int8 weights (half the HBM bytes of bf16,
native MXU int8 multiply on v5e) should approach 2x on the matmul-
dominated portion.  This measures the raw op; model integration
follows only if the chip confirms the win.

    python tools/bench_int8_matmul.py [--iters 30]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SHAPES = [  # (B, H, O): lm head, MLP up, MLP down, qkv at gpt2-small
    (8, 768, 50304),
    (8, 768, 3072),
    (8, 3072, 768),
    (8, 768, 2304),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--iters', type=int, default=30)
    args = ap.parse_args()

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    import jax.numpy as jnp
    from paddle_tpu.ops.int8_matmul import (quantize_weight_int8,
                                            dynamic_int8_matmul)
    print(f'device: {jax.devices()[0]}', file=sys.stderr)
    rs = np.random.RandomState(0)
    rows = {}
    for B, H, O in SHAPES:
        x = jnp.asarray(rs.randn(B, H), jnp.bfloat16)
        w = jnp.asarray(rs.randn(H, O) / np.sqrt(H), jnp.float32)
        w_bf = w.astype(jnp.bfloat16)
        w_q, w_s = quantize_weight_int8(w)

        def chain(fn, x):
            # in-graph chain with a data dependency amortizes
            # dispatch overhead; fold the output
            # back to the input width via a cheap slice-sum
            def body(c, _):
                y = fn(c)
                return (c + y[:, :H].astype(c.dtype)
                        if O >= H else c + jnp.pad(y, ((0, 0), (0, H - O))).astype(c.dtype)), None
            out, _ = jax.lax.scan(body, x, None, length=args.iters)
            return out

        f_bf = jax.jit(lambda x: chain(lambda c: c @ w_bf, x))
        f_i8 = jax.jit(lambda x: chain(
            lambda c: dynamic_int8_matmul(c, w_q, w_s), x))
        out = {}
        for name, f in (('bf16', f_bf), ('int8', f_i8)):
            float(np.asarray(f(x)).ravel()[0])     # compile+warm
            t0 = time.perf_counter()
            float(np.asarray(f(x)).ravel()[0])
            out[name] = (time.perf_counter() - t0) * 1e3 / args.iters
        rows[f'{B}x{H}x{O}'] = out
        print(f'[{B}x{H}x{O}] bf16 {out["bf16"]:7.3f} ms  '
              f'int8 {out["int8"]:7.3f} ms  '
              f'({out["bf16"] / out["int8"]:.2f}x)', file=sys.stderr)
    import json
    print(json.dumps(rows))


if __name__ == '__main__':
    main()
