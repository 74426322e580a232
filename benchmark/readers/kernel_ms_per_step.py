"""Device time of the kernels whose instruction name matches `pattern`,
per traced step, on the first chip, in milliseconds."""


def read(params, ctx):
    trace = ctx['trace']
    steps = ctx['counters'].get('traced_steps')
    if trace is None or not steps:
        return None
    total_ns, calls = trace.kernel(params['pattern'])
    if not calls:
        return None
    return total_ns / 1e6 / steps
