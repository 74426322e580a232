"""A decode kernel's share of its roofline for the hybrid Mamba-2 /
attention decoder, from the spans: what the `serve.decode_dispatch`
spans begun in the window say their token steps did (`what`:
`ssm_decode`, the live rows whose states they rewrote, `state_rows`,
each row's state read and written once in every Mamba layer;
`paged`, the KV blocks a layer read, `kv_blocks`, in every attention
layer: `benchmark/granite_flops.py`), over the device self time of the
ops under `scope` on the first chip, at the roof of the chip's peaks
(`benchmark/peaks.json`), in percent.  Both sides are of the same
dispatches: those whose execution of `module` lies whole in the window
(`read_by`, `module`: `span_args`'s).  None without a chip trace or
where no span carries the argument."""
from benchmark import granite_flops as gf
from benchmark.readers import span_args


def read(params, ctx):
    arg = {'ssm_decode': 'state_rows', 'paged': 'kv_blocks'}[params['what']]
    found = [(args[arg], run) for args, run in span_args.spans(
                 ctx, 'serve.decode_dispatch', params['read_by'],
                 params['module'])
             if arg in args]
    if not found:
        return None
    total_ns, ops = span_args.scope_ns(ctx, params['scope'],
                                       sorted(run for _, run in found))
    if not ops or not total_ns:
        return None
    config = ctx['config']
    model, done = config['model'], sum(n for n, _ in found)
    if params['what'] == 'ssm_decode':
        need_ops, need_bytes = gf.ssm_decode_update(model)
        mamba, _ = gf.layers_of(model)
        need = (need_ops * done * mamba, need_bytes * done * mamba)
    else:
        need = gf.paged_read(model, config['serve']['block_size'], done)
    least_s, _bound = gf.least_seconds(*need, gf.peaks(ctx['device_kind']))
    return 100.0 * least_s / (total_ns / 1e9)
