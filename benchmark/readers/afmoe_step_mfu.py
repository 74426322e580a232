"""The whole window's share of the chip's bf16 peak for the routed
decoder with a shared expert: the operations of the true prompt tokens
prefilled and of the tokens decoded (benchmark/afmoe_flops.py: active
weights only, the dense MLP in the dense layers, attention at each
query's own band) over the counted window, over the peak of the chips
the cell asks for, in percent.  It bounds any later claim whatever
implements a layer.  A chip's number only."""
from benchmark import afmoe_flops as af


def read(params, ctx):
    c = ctx['counters']
    positions = c.get('context_positions')
    if not c.get('window_ms') or not positions or not ctx['on_tpu'] \
            or c.get('prefill_tokens') is None:
        return None
    ops = af.window_ops(
        ctx['config']['model'], prefill_tokens=c['prefill_tokens'],
        decoded_tokens=c['decoded_tokens'], positions=positions)
    peak = af.peaks(ctx['device_kind'])['bf16_flops_per_s']
    return 100.0 * ops / (c['window_ms'] / 1e3) / (ctx['chips'] * peak)
