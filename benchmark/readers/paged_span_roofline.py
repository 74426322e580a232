"""The paged decode kernel's share of its roofline, from the spans:
the bytes of the KV blocks that the `serve.decode_dispatch` spans
begun in the window read, by their `kv_blocks` arguments (a layer's
blocks summed over a dispatch's token steps, each step up to its own
length, as the kernel reads them: `serving/engine.py`), times a
block's keys and values in every layer of the cell's configuration,
over the device self time under `scope` on the first chip at the
chip's HBM speed (`benchmark/peaks.json`), in percent.  Both sides are
of the same dispatches: those whose execution of `module` lies whole
in the window (`read_by`, `module`: `span_args`'s), so the dispatch in
flight as the window opens and the one sent as it closes count on
neither.  None without a chip trace or where no span carries
`kv_blocks`."""
from benchmark import flops
from benchmark.readers import span_args


def block_bytes(config):
    """Bytes of one KV block's keys and values over every layer."""
    import jax.numpy as jnp
    model = config['model']
    heads = model.get('num_kv_heads', model['num_heads'])
    head_dim = model.get('head_dim',
                         model['hidden_size'] // model['num_heads'])
    itemsize = jnp.dtype(config['kv_pool']['dtype']).itemsize
    return (config['serve']['block_size'] * heads * head_dim * 2
            * itemsize * model['num_layers'])


def read(params, ctx):
    found = [(args['kv_blocks'], run) for args, run in span_args.spans(
                 ctx, 'serve.decode_dispatch', params['read_by'],
                 params['module'])
             if 'kv_blocks' in args]
    if not found:
        return None
    total_ns, ops = span_args.scope_ns(ctx, params['scope'],
                                       sorted(run for _, run in found))
    if not ops or not total_ns:
        return None
    need_s = sum(n for n, _ in found) * block_bytes(ctx['config']) \
        / flops.peaks(ctx['device_kind'])['hbm_bytes_per_s']
    return 100.0 * need_s / (total_ns / 1e9)
