"""A number the runner counted, times `scale`."""


def read(params, ctx):
    value = ctx['counters'].get(params['key'])
    if value is None:
        return None
    return value * params.get('scale', 1.0)
