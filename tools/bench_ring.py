#!/usr/bin/env python
"""Ring-attention evidence on the virtual CPU mesh.

Two claims, two measurements suited to THIS box (devices are
time-sliced on one core, so wall-clock tracks TOTAL work, while the
striped layout's win is about the per-step CRITICAL PATH on parallel
hardware):

1. MEASURED — the causal ring's lax.cond skip of fully-masked future
   blocks: causal wall-clock should be ~half of non-causal on the
   serialized mesh (the skip removes ~half the total block FLOPs).
2. EXACT SCHEDULE — per-device flash-kernel tile counts for the
   contiguous vs striped layouts.  The busiest device bounds the
   per-step critical path on real parallel chips; striping halves it.

    python tools/bench_ring.py [--t 2048] [--bh 4] [--d 64] [--sp 4]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def tile_counts(sp, nq, nk):
    """Flash-kernel tiles computed per device over a full ring pass
    (the pl.when skip drops tiles above the causal diagonal)."""
    full = nq * nk
    diag = sum(min(nk, (qi * 1 + 1)) for qi in range(nq))  # bq == bk
    strict = diag  # same skip bound; the extra masked diagonal tile
    #                is zeroed in-kernel, not skipped
    contig = [r * full + diag for r in range(sp)]
    striped = [(r + 1) * diag + (sp - 1 - r) * strict
               for r in range(sp)]
    return contig, striped


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--t', type=int, default=2048)
    ap.add_argument('--bh', type=int, default=4)
    ap.add_argument('--d', type=int, default=64)
    ap.add_argument('--sp', type=int, default=4)
    ap.add_argument('--iters', type=int, default=5)
    args = ap.parse_args()

    # CPU-only by design (the ring needs sp>1 devices and compares
    # numerics and schedule shape, not device time): force the virtual
    # CPU mesh before any backend initializes.
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '')
        + f' --xla_force_host_platform_device_count={args.sp}')

    import functools
    import numpy as np
    import jax
    jax.config.update('jax_platforms', 'cpu')
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    jax.config.update('jax_num_cpu_devices', args.sp)
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.ops.ring_attention import ring_attention

    rs = np.random.RandomState(0)
    BH, T, D, SP = args.bh, args.t, args.d, args.sp
    q, k, v = (jnp.asarray(rs.randn(BH, T, D), jnp.float32)
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:SP]).reshape(SP), ('sp',))
    spec = P(None, 'sp', None)

    def ring(causal):
        return jax.jit(jax.shard_map(
            functools.partial(ring_attention, axis_name='sp',
                              causal=causal, use_flash=False),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))

    def timeit(fn, *xs):
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3

    ms_full = timeit(ring(False), q, k, v)
    ms_causal = timeit(ring(True), q, k, v)
    print(f'T={T} sp={SP} bh={BH} d={D} (einsum engine, serialized '
          f'CPU mesh -> wall-clock == total FLOPs)', file=sys.stderr)
    print(f'non-causal ring (all blocks): {ms_full:8.1f} ms',
          file=sys.stderr)
    print(f'causal ring (cond skip):      {ms_causal:8.1f} ms  '
          f'({ms_full / ms_causal:.2f}x less work)', file=sys.stderr)

    t_local = T // SP
    nq = nk = max(1, t_local // 128)
    contig, striped = tile_counts(SP, nq, nk)
    print(f'flash tile schedule (per-device, one ring pass, '
          f'{nq}x{nk} tiles/block):', file=sys.stderr)
    print(f'  contiguous: {contig}  max={max(contig)}', file=sys.stderr)
    print(f'  striped:    {striped}  max={max(striped)}',
          file=sys.stderr)
    print(f'  critical-path ratio (contig/striped): '
          f'{max(contig) / max(striped):.2f}x on parallel devices',
          file=sys.stderr)
    import json
    print(json.dumps({
        'noncausal_ms': ms_full, 'causal_ms': ms_causal,
        'skip_work_ratio': ms_full / ms_causal,
        'tiles_contig_max': max(contig),
        'tiles_striped_max': max(striped),
        'critical_path_ratio': max(contig) / max(striped)}))


if __name__ == '__main__':
    main()
