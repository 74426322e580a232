#!/usr/bin/env python
"""Time `paged_decode_grouped` alone at the routed serving cells'
operands, per round size (`chunk`).

Run ON THE REAL CHIP.  The round size `paged_attention` resolves is
`ops/paged_attention.py::_blocks_a_round_grouped`; a change to its
rule comes with this sweep's readings in PERF.md.

    python tools/time_paged_grouped.py
    python tools/time_paged_grouped.py --cells moe_shared --chunks 8,16

Each cell's operands come from --seed: float32 pools folded to
[blocks, 16, 4 x 128], tables of random blocks, and context lengths
drawn like the cell's; a window layer's rows see the last `window`
positions of their context, a full layer's all of it.  A line gives
the kernel's device time a call (a profiler trace of --calls calls)
and its share of the HBM roof: the blocks the kernel fetches, K and
V, from each row's first visible block to its last, at 819 GB/s.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9       # one TPU v5e (benchmark/peaks.json)

# rows, query heads, pool blocks, table width, window, and the
# contexts: (share of rows, least, most) drawn uniformly
CELLS = {
    # trinity_mini_serve, serve_backlog_moe_shared_decode: prompts
    # 128-2,048, answers 512-4,096
    'moe_shared': dict(rows=64, heads=32, blocks=8321, width=384,
                       window=2048, contexts=((1.0, 128, 3000),)),
    # smallthinker_21b_serve, serve_backlog_moe_window: prompts
    # 512-12,288, 11 of 32 over the window
    'moe_window': dict(rows=32, heads=28, blocks=8257, width=800,
                       window=4096, contexts=((0.3, 600, 12800),
                                              (0.7, 600, 4096))),
}
KV_HEADS, HEAD_DIM, BLOCK = 4, 128, 16


def operands(cell, seed, full):
    """q, pools, tables, lens, first of one layer, on the device."""
    import numpy as np
    import jax.numpy as jnp
    c = CELLS[cell]
    rs = np.random.RandomState(seed % 2 ** 32)
    lens = np.concatenate([
        rs.randint(lo, hi + 1, int(round(share * c['rows'])))
        for share, lo, hi in c['contexts']])[:c['rows']]
    lens = np.minimum(lens, c['width'] * BLOCK).astype(np.int32)
    first = (np.zeros_like(lens) if full
             else np.maximum(lens - c['window'], 0).astype(np.int32))
    tables = rs.randint(1, c['blocks'], (c['rows'], c['width']))
    shape = (c['blocks'], BLOCK, KV_HEADS * HEAD_DIM)
    kp = rs.standard_normal(shape).astype(np.float32)
    vp = rs.standard_normal(shape).astype(np.float32)
    q = rs.standard_normal((c['rows'], c['heads'], HEAD_DIM))
    return tuple(jnp.asarray(x) for x in (
        q.astype(np.float32), kp, vp, tables.astype(np.int32), lens,
        first))


def fetched_bytes(lens, first, width):
    """K and V bytes of the blocks the kernel copies, as its `span`."""
    import numpy as np
    hi = np.clip(-(-np.asarray(lens) // BLOCK), 1, width)
    lo = np.clip(np.asarray(first) // BLOCK, 0, hi - 1)
    return int((hi - lo).sum()) * 2 * BLOCK * KV_HEADS * HEAD_DIM * 4


def kernel_ms(decode, ops, chunk, calls, name='paged_decode_grouped'):
    """Device ms a call of `decode(*ops, chunk=chunk)`, from a trace."""
    import jax
    from benchmark.reduce_trace import Trace, find_xplane
    fn = jax.jit(lambda *a: decode(*a, chunk=chunk))
    fn(*ops).block_until_ready()
    trace_dir = tempfile.mkdtemp(prefix='paged_grouped_')
    with jax.profiler.trace(trace_dir):
        for _ in range(calls):
            out = fn(*ops)
        out.block_until_ready()
    ns, n = Trace.from_file(find_xplane(trace_dir)).kernel(name)
    return ns / max(n, 1) / 1e6, n


def sweep(decode, cells, chunks, seed, calls, label=''):
    """One line a (cell, layer kind, chunk)."""
    rows = []
    for cell in cells:
        for full in (False, True):
            ops = operands(cell, seed, full)
            need = fetched_bytes(ops[4], ops[5], CELLS[cell]['width'])
            for chunk in chunks:
                ms, n = kernel_ms(decode, ops, chunk, calls)
                share = need / HBM_BYTES_PER_S / (ms / 1e3) * 100
                row = dict(kernel=label, cell=cell,
                           layer='full' if full else 'window',
                           chunk=chunk, ms=ms, calls=n,
                           mb=need / 1e6, roofline=share)
                rows.append(row)
                print(f'{label:>8} {cell:>10} {row["layer"]:>6} '
                      f'chunk={chunk:<3} {ms:.4f} ms a call ({n} calls)'
                      f'  {need / 1e6:.2f} MB  roofline {share:.1f}%',
                      flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--cells', default=','.join(CELLS))
    ap.add_argument('--chunks', default='8,16,32')
    ap.add_argument('--seed', type=int, default=2147483011)
    ap.add_argument('--calls', type=int, default=20)
    args = ap.parse_args()

    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    from paddle_tpu.ops import paged_attention as pa
    sweep(pa._paged_decode_grouped, args.cells.split(','),
          [int(c) for c in args.chunks.split(',')], args.seed,
          args.calls, label='kernel')


if __name__ == '__main__':
    main()
