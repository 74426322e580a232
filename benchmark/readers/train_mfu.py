"""Model FLOP/s utilization: operations a trained token requires
(benchmark/flops.py) times the tokens per second of this run's window,
over the bf16 peak of the chips the cell asks for, in percent.  Device
numbers come from a chip only."""
from benchmark import flops


def read(params, ctx):
    rate = ctx['counters'].get('tokens_per_s')
    chips = ctx['chips']
    if rate is None or not ctx['on_tpu']:
        return None
    peak = flops.peaks(ctx['device_kind'])['bf16_flops_per_s']
    per_token = flops.train_flops_per_token(
        ctx['config']['model'], ctx['traffic']['seq_len'])
    return 100.0 * rate * per_token / (chips * peak)
