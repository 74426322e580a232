"""One of the runner's counters over another, times `scale`."""


def read(params, ctx):
    num = ctx['counters'].get(params['num'])
    den = ctx['counters'].get(params['den'])
    if num is None or not den:
        return None
    return num / den * params.get('scale', 1.0)
