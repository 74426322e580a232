"""Compile the serving engine's modules of `serve_backlog_mamba_hybrid`
for a described v5e from shapes alone (no model, pool or state is
allocated) and print each module's memory: what sized the KV pool of
benchmark/configs/granite_4_0_h_micro_serve.json (every module under
15.75 GiB less 1 GB, PERF.md section 4).

    JAX_PLATFORMS=cpu python3 tests/benchmark_suite/granite_compile_report.py [--num-blocks N]

Runs on the CPU with the TPU compiler; a compile is not a run.  Prints
one JSON line a module and a last line with the largest."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

LIMIT = 15.75 * 2 ** 30 - 1e9


def engine_of_shapes(config, one_chip, num_blocks=None):
    """A ServingEngine object for `config` whose parameters, pools and
    states are ShapeDtypeStructs on the described chip."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)
    from paddle_tpu.serving import ServeConfig, ServingEngine
    from paddle_tpu.serving.kv_cache import HybridCache
    from paddle_tpu.serving.scheduler import ContinuousBatchingScheduler
    from benchmark.reference import granite_ref
    from benchmark.runners.serve_routed import model_kwargs

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    m = config['model']
    cfg = GraniteHybridConfig(dtype=config['weights_dtype'],
                              **model_kwargs(config))
    model = GraniteHybridForCausalLM.__new__(GraniteHybridForCausalLM)
    model.config = cfg
    serve = dict(config['serve'])
    if num_blocks is not None:
        serve['num_blocks'] = int(num_blocks)
    eng = ServingEngine.__new__(ServingEngine)
    eng.model = model
    eng.config = ServeConfig(**serve).resolved(cfg)
    eng.cache = cache = HybridCache(
        **model.cache_spec(), block_size=eng.config.block_size,
        num_blocks=eng.config.num_blocks, slots=eng.config.max_slots,
        max_model_len=eng.config.max_model_len, device_init=False)
    pool = sd((cache.kv.num_blocks, cache.block_size,
               cache.num_kv_heads * cache.head_dim), 'float32')
    cache.pools = [(pool, pool)] * cache.layer_kinds.count('kv')
    cache.state.states = [
        tuple(sd((cache.slots,) + s, 'float32') for s in cache.state.shapes)
    ] * cache.state.num_layers
    eng.scheduler = ContinuousBatchingScheduler(
        cache, max_slots=eng.config.max_slots,
        batch_buckets=eng.config.batch_buckets, bucket_fn=lambda n: n,
        max_model_len=eng.config.max_model_len,
        decode_span=eng.config.decode_span)
    dtypes = {'dt_bias': 'float32', 'A_log': 'float32', 'D': 'float32'}
    eng._params = {k: sd(s, dtypes.get(k.rsplit('.', 1)[-1],
                                       config['weights_dtype']))
                   for k, s in granite_ref.shapes(m).items()}
    eng._buffers = {}
    eng._last = sd((eng.config.max_slots + 1,), 'int32')
    return eng


def module_args(eng, kind, rows, width, one_chip):
    """(fn, example avals, donate) of one module, as the engine builds
    and calls it."""
    import jax
    import jax.numpy as jnp

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: sd(a.shape, a.dtype), tree)

    arrays = eng.cache.arrays()
    row = sd((rows,), 'int32')
    if kind == 'decode':
        fn = eng._decode_build(rows, eng.config.decode_span)
        where = on_chip(eng.cache.idle_where(rows, width))
        example = (eng._params, {}, *arrays, eng._last, where, row, row,
                   sd((rows,), 'bool'), row, row)
        return fn, example, (2, 3, 4)
    fn = eng._prefill_build(width, rows)
    where = on_chip(eng.cache.prefill_where((), rows, width))
    example = (eng._params, {}, sd((rows, width), 'int32'), row, *arrays,
               eng._last, where, row, row)
    return fn, example, (4, 5, 6)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--num-blocks', type=int, default=None)
    ap.add_argument('--config', default=os.path.join(
        REPO, 'benchmark/configs/granite_4_0_h_micro_serve.json'))
    args = ap.parse_args(argv)
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import _gating
    jax.config.update('jax_enable_compilation_cache', False)
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    one_chip = SingleDeviceSharding(topo.devices[0])
    # the chip's paths: a TPU is what the modules are compiled for
    _gating.pallas_backend_ok = lambda: True
    with open(args.config) as f:
        config = json.load(f)
    eng = engine_of_shapes(config, one_chip, args.num_blocks)
    W = eng.scheduler.table_width
    modules = [('decode', b, W) for b in eng.config.batch_buckets] \
        + [('prefill', 1, p) for p in eng.config.prompt_buckets]
    worst = 0
    for kind, rows, width in modules:
        fn, example, donate = module_args(eng, kind, rows, width, one_chip)
        compiled = jax.jit(fn, donate_argnums=donate).lower(
            *example).compile()
        mem = compiled.memory_analysis()
        total = mem.argument_size_in_bytes + mem.output_size_in_bytes \
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
        worst = max(worst, total)
        text = compiled.as_text()
        print(json.dumps({
            'module': f'{kind}[{rows}x{width}]',
            'argument_bytes': mem.argument_size_in_bytes,
            'output_bytes': mem.output_size_in_bytes,
            'alias_bytes': mem.alias_size_in_bytes,
            'temp_bytes': mem.temp_size_in_bytes, 'total_bytes': total,
            'kernels': sorted(k for k in ('ssm_decode',
                                          'paged_decode_grouped',
                                          'flash_fwd') if k in text)}),
            flush=True)
    print(json.dumps({'num_blocks': eng.config.num_blocks,
                      'pool_bytes': eng.cache.pool_bytes,
                      'state_bytes': eng.cache.state_bytes,
                      'largest_module_bytes': worst, 'limit_bytes': LIMIT,
                      'fits': worst <= LIMIT}))


if __name__ == '__main__':
    main()
