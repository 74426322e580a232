"""The flash-attention forward kernel's share of its roofline: the
least time the chip could take for one call's operations and bytes
(benchmark/flops.py, from the cell's shapes) over the measured mean
time of a call, in percent."""
from benchmark import flops


def read(params, ctx):
    trace = ctx['trace']
    if trace is None:
        return None
    total_ns, calls = trace.kernel(params['pattern'])
    if not calls:
        return None
    need_ops, need_bytes = flops.flash_fwd_call(
        ctx['config']['model'], ctx['traffic']['batch'],
        ctx['traffic']['seq_len'])
    least_s, _bound = flops.least_seconds(
        need_ops, need_bytes, flops.peaks(ctx['device_kind']))
    return 100.0 * least_s / (total_ns / 1e9 / calls)
