"""The expanded prefill's flash kernel's share of its roofline, from
the spans: the operations of the causal (query, key) pairs of true
prompt positions that the `serve.prefill_dispatch` spans begun in the
window carry (`attn_pairs`), in every head and layer
(`benchmark/mla_flops.py::prefill_flash`), over the device self time
of the ops under `scope` (the kernel's name) on the first chip at the
chip's bf16 peak, in percent.  Both sides are of the prefills whose
execution of `module` lies whole in the window (`read_by`, `module`:
`span_args`'s).  None without a chip trace or where no span carries
`attn_pairs`."""
from benchmark import mla_flops
from benchmark.readers import span_args


def read(params, ctx):
    found = [(args['attn_pairs'], run) for args, run in span_args.spans(
                 ctx, 'serve.prefill_dispatch', params['read_by'],
                 params['module'])
             if 'attn_pairs' in args]
    if not found:
        return None
    total_ns, ops = span_args.scope_ns(ctx, params['scope'],
                                       sorted(run for _, run in found))
    if not ops or not total_ns:
        return None
    need, _ = mla_flops.prefill_flash(ctx['config']['model'],
                                      sum(n for n, _ in found))
    need_s = need / mla_flops.peaks(ctx['device_kind'])['bf16_flops_per_s']
    return 100.0 * need_s / (total_ns / 1e9)
