"""Share of the traced span in which no operation ran on the device:
1 - union of the chip's 'XLA Ops' intervals over the span, in percent,
averaged over the chips."""


def read(params, ctx):
    summary = ctx['trace_summary']
    if summary is None:
        return None
    return 100.0 * (1.0 - summary['busy_s'] / summary['window_s'])
