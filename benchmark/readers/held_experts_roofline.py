"""The held experts' decode product's share of its roofline, from the
spans: the bytes of the held experts the decode dispatches' token steps
hit (`moe_experts_hit` of each dispatch's `serve.absorb` span, summed
over token steps and routed layers: an expert read and not needed
counts against the program), over the device self time of the ops
under `scope` inside those dispatches' module executions on the first
chip at the chip's HBM speed, in percent.  The dispatches are those of
`serve.decode_dispatch` begun in the window whose execution of `module`
lies whole in it (`read_by`: `span_args`'s); a dispatch's absorb, which
the host runs after the execution, is found by its number wherever it
began.  None without a chip trace or where no absorb carries
`moe_experts_hit`."""
from benchmark import mla_flops
from benchmark.readers import span_args


def read(params, ctx):
    got = span_args._parsed(ctx)
    if got is None:
        return None
    hits = {args['dispatch']: args['moe_experts_hit']
            for name, _, _, args in got[1]
            if name == 'serve.absorb' and 'moe_experts_hit' in args}
    found = [(hits[args['dispatch']], run) for args, run in span_args.spans(
                 ctx, 'serve.decode_dispatch', params['read_by'],
                 params['module'])
             if args.get('dispatch') in hits]
    if not found:
        return None
    total_ns, ops = span_args.scope_ns(ctx, params['scope'],
                                       sorted(run for _, run in found))
    if not ops or not total_ns:
        return None
    _, need = mla_flops.experts_stream(ctx['config']['model'],
                                       sum(n for n, _ in found))
    need_s = need / mla_flops.peaks(ctx['device_kind'])['hbm_bytes_per_s']
    return 100.0 * need_s / (total_ns / 1e9)
