"""The whole window's share of the chip's bf16 peak for the latent-
attention decoder with held experts: the operations of the true prompt
tokens prefilled and of the tokens decoded (`benchmark/mla_flops.py::
window_ops`: every matrix a token meets, the held experts it was routed
to, the head once a delivered token, a prefill's causal pairs expanded
and a decoded token's keys absorbed) over the counted window, over the
peak of the chips the cell asks for, in percent.  A chip's number
only."""
from benchmark import mla_flops


def read(params, ctx):
    c = ctx['counters']
    positions = c.get('context_positions')
    if not c.get('window_ms') or not positions or not ctx['on_tpu'] \
            or c.get('prefill_tokens') is None \
            or c.get('moe_assignments') is None:
        return None
    ops = mla_flops.window_ops(
        ctx['config']['model'], prefill_tokens=c['prefill_tokens'],
        decoded_tokens=c['decoded_tokens'],
        decode_assignments=c['moe_assignments'],
        prefill_pairs=positions['prefill_full'],
        decode_keys=positions['decode_full'])
    peak = mla_flops.peaks(ctx['device_kind'])['bf16_flops_per_s']
    return 100.0 * ops / (c['window_ms'] / 1e3) / (ctx['chips'] * peak)
