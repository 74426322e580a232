"""The latent decode kernel's share of its roofline, from the spans:
the bytes the latent blocks that the `serve.decode_dispatch` spans
begun in the window read, by their `kv_blocks` arguments (a layer's
blocks summed over a dispatch's token steps, each step up to its own
length, as the kernel reads them), need in every layer of the cell's
configuration (`benchmark/mla_flops.py::latent_read`: a position's
latent and rotary key, not the lanes a pool row is padded with), over
the device self time of the ops under `scope` (the kernel's name) on
the first chip at the chip's HBM speed, in percent.  Both sides are of
the same dispatches: those whose execution of `module` lies whole in
the window (`read_by`, `module`: `span_args`'s).  None without a chip
trace or where no span carries `kv_blocks`."""
from benchmark import mla_flops
from benchmark.readers import span_args


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
    config = ctx['config']
    _, need = mla_flops.latent_read(
        config['model'], config['serve']['block_size'],
        sum(n for n, _ in found))
    need_s = need / mla_flops.peaks(ctx['device_kind'])['hbm_bytes_per_s']
    return 100.0 * need_s / (total_ns / 1e9)
