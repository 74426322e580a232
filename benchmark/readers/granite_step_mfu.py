"""The whole window's share of the chip's bf16 peak for the hybrid
Mamba-2 / attention decoder: the operations of the true prompt tokens
prefilled and of the tokens decoded (`benchmark/granite_flops.py::
window_ops`: every matrix a token meets, the head once a delivered
token, the state's update in every Mamba layer, the keys every query
saw in every attention layer) over the counted window, over the peak
of the chips the cell asks for, in percent.  A chip's number only."""
from benchmark import granite_flops


def read(params, ctx):
    c = ctx['counters']
    positions = c.get('context_positions')
    if not c.get('window_ms') or not positions or not ctx['on_tpu'] \
            or c.get('prefill_tokens') is None:
        return None
    ops = granite_flops.window_ops(
        ctx['config']['model'], prefill_tokens=c['prefill_tokens'],
        decoded_tokens=c['decoded_tokens'], positions=positions)
    peak = granite_flops.peaks(ctx['device_kind'])['bf16_flops_per_s']
    return 100.0 * ops / (c['window_ms'] / 1e3) / (ctx['chips'] * peak)
