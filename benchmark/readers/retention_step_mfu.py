"""The whole decode step's share of the chip's bf16 peak for the
retention decoder: operations a served token needs
(benchmark/retention_flops.py) times the tokens a second the counted
part of the run decoded, over the peak of the chips the cell asks for,
in percent.  It stands beside `retention_decode_roofline.*`: a change
that takes the kernel off the path leaves that one silent, and this one
still bounds what it may claim.  Prefill's operations are not counted,
so it reads a little low and never high.  A chip's number only."""
from benchmark import retention_flops


def read(params, ctx):
    tokens = ctx['counters'].get('decoded_tokens')
    window_ms = ctx['counters'].get('window_ms')
    if not tokens or not window_ms or not ctx['on_tpu']:
        return None
    peak = retention_flops.peaks(ctx['device_kind'])['bf16_flops_per_s']
    per_token = retention_flops.decode_step_ops_per_token(
        ctx['config']['model'])
    return 100.0 * per_token * tokens / (window_ms / 1e3) \
        / (ctx['chips'] * peak)
