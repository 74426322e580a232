"""Device self time of the ops whose op_name carries `scope` (a regex,
searched along the whole path of jax name scopes, so forward, backward
and inner scopes count), on the first chip inside the traced span, in
milliseconds per unit of work: per `per_counter` (one of the runner's
counters, such as traced_steps), or per `per_span` (the program's host
spans of that name begun inside the traced span) times the number at
`times_config` (a path of keys into the configuration)."""
from benchmark import scoped_trace


def read(params, ctx):
    st = scoped_trace.for_ctx(ctx)
    if st is None:
        return None
    total_ns, ops = st.scope_ns(params['scope'])
    if 'per_counter' in params:
        units = ctx['counters'].get(params['per_counter'])
    else:
        units = len(st.begun(params['per_span']))
        if 'times_config' in params:
            times = ctx['config']
            for key in params['times_config']:
                times = times[key]
            units *= times
    if not ops or not units:
        return None
    return total_ns / 1e6 / units
