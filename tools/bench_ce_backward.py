#!/usr/bin/env python
"""Micro-benchmark: WHY is the unfused CE head slow on TPU?

Hypothesis (round-4 chip session 2): the backward of the hard-label
gather (`take_along_axis`) is a scatter-add into the [B*T, V] logits
buffer, which XLA lowers to a serialized scatter on TPU.  The classic
fix is the fused softmax-CE backward: d logits = softmax - one_hot,
dense elementwise math, no scatter.

Times three formulations of mean-NLL at GPT-2 bench shape
([8192, 50304] bf16 logits) on the live device:

  gather   : -take_along_axis(log_softmax(x))         (autodiff scatter)
  onehot   : -sum(one_hot * log_softmax(x))           (dense fwd+bwd)
  customvjp: paddle_tpu F.cross_entropy               (whatever it does now)

Usage: python tools/bench_ce_backward.py [--n 8192] [--v 50304]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def timeit(fn, *args, iters=10):
    import jax

    def barrier(o):
        # single-ELEMENT readback: a full np.asarray would copy the
        # [N, V] gradient to the host inside the timed region,
        # swamping the fast arms' few-ms steps
        return float(np.asarray(o.reshape(-1)[0]))

    out = fn(*args)
    jax.block_until_ready(out)
    barrier(out)                                # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    barrier(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--n', type=int, default=8192)
    ap.add_argument('--v', type=int, default=50304)
    ap.add_argument('--iters', type=int, default=10)
    args = ap.parse_args()

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    import jax.numpy as jnp
    print(f'device: {jax.devices()[0]}', file=sys.stderr)

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(args.n, args.v), jnp.bfloat16)
    lab = jnp.asarray(rs.randint(0, args.v, size=(args.n,)), jnp.int32)

    def nll_gather(x, lab):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], axis=-1).mean()

    def nll_onehot(x, lab):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        oh = (lab[:, None] == jnp.arange(x.shape[-1])[None, :])
        return -jnp.sum(jnp.where(oh, logp, 0.0)) / x.shape[0]

    def nll_paddle(x, lab):
        import paddle_tpu.nn.functional as F
        from paddle_tpu import Tensor
        return F.cross_entropy(Tensor(x), Tensor(lab)).value

    rows = {}
    for name, fn in [('gather', nll_gather), ('onehot', nll_onehot),
                     ('paddle', nll_paddle)]:
        g = jax.jit(jax.grad(fn))
        ms = timeit(g, x, lab, iters=args.iters)
        rows[name] = ms
        print(f'{name:8s} grad: {ms:8.2f} ms', file=sys.stderr, flush=True)
    import json
    print(json.dumps(rows))


if __name__ == '__main__':
    main()
