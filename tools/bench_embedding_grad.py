#!/usr/bin/env python
"""Embedding-gradient strategy A/B at GPT bench shapes (round-5 CPU
census lead: the wte scatter-add is 5.5% of step bytes and the last
remaining scatter in the train step — the op class whose serialized
form cost 6.66x in the CE head, PERF.md round 4).

Strategies for dW[V,H] from ids[N] and upstream g[N,H]:
  scatter     — zeros.at[ids].add(g): the current XLA lowering of the
                embedding-lookup vjp (row-wise scatter-add).
  onehot_dot  — one_hot(ids)[N,V]^T @ g -> dot_general on the MXU;
                trades an 824 MB bf16 one-hot operand for zero scatter
                (HBM-roofline ~1 ms at v5e: may still win if scatter
                serializes).
  sort_seg    — sort ids, segment_sum over sorted rows (XLA lowers the
                segment sum to a scatter over a SORTED index vector,
                which the TPU backend can turn into windowed adds).

Prints one JSON line {strategy: ms}.  Chip verdict decides whether the
embedding vjp gets a custom dense path (like _softmax_nll did).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--smoke', action='store_true')
    ap.add_argument('--vocab', type=int, default=50304)
    ap.add_argument('--hidden', type=int, default=768)
    ap.add_argument('--tokens', type=int, default=8 * 1024)
    ap.add_argument('--iters', type=int, default=30)
    args = ap.parse_args()
    if args.smoke:
        args.vocab, args.tokens, args.iters = 1024, 512, 3

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    import jax.numpy as jnp
    from jax import lax

    V, H, N = args.vocab, args.hidden, args.tokens
    print(f'device: {jax.devices()[0]}  V={V} H={H} N={N}',
          file=sys.stderr)
    rs = np.random.RandomState(0)
    ids = jax.device_put(rs.randint(0, V, size=N).astype('int32'))
    g = jax.device_put(rs.randn(N, H).astype('float32')
                       .astype('bfloat16'))

    def dw_scatter(ids, g):
        return jnp.zeros((V, H), jnp.float32).at[ids].add(
            g.astype(jnp.float32))

    def dw_onehot_dot(ids, g):
        oh = jax.nn.one_hot(ids, V, dtype=jnp.bfloat16)      # [N, V]
        return lax.dot_general(
            oh, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [V, H]

    def dw_sort_seg(ids, g):
        order = jnp.argsort(ids)
        # indices_are_sorted is the whole point of this strategy: it
        # sets the hint on the lowered scatter so the TPU backend can
        # use windowed adds instead of the generic path
        return jax.ops.segment_sum(
            g[order].astype(jnp.float32), ids[order], num_segments=V,
            indices_are_sorted=True)

    impls = {'scatter': dw_scatter, 'onehot_dot': dw_onehot_dot,
             'sort_seg': dw_sort_seg}
    ref = None
    out = {}
    for name, fn in impls.items():
        jf = jax.jit(fn)
        dw = jf(ids, g)
        jax.block_until_ready(dw)
        got = np.asarray(dw, dtype='float64')
        if ref is None:
            ref = got
        else:       # all strategies must agree (bf16-level tolerance)
            np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)
        t0 = time.time()
        for _ in range(args.iters):
            dw = jf(ids, g)
        jax.block_until_ready(dw)
        # scalar-slice barrier: a full [V,H] readback (~154 MB) would
        # swamp the 1-2 ms kernel deltas this bench discriminates
        float(np.asarray(dw[0, 0]))
        dt = (time.time() - t0) / args.iters * 1e3
        out[name] = round(dt, 3)
        print(f'{name}: {dt:.3f} ms', file=sys.stderr)
    print(json.dumps(out))


if __name__ == '__main__':
    main()
