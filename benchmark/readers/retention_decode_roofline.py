"""The retention decode update's share of its roofline: the least time
the chip could take for the state updates of the traced window
(benchmark/retention_flops.py, one update a live row and layer) over
the device self time of every op under the scope `scope` there, the
feature maps and the normaliser beside the kernel included, in percent.

Live rows of the traced window: the `serve.decode_dispatch` spans begun
in it, times the span's token steps, times the run's mean live rows a
token step (`state_rows_updated` over `token_steps`, the engine's own
counters: rows that are padding or have ended are not counted, so a
kernel that skips them cannot read above 100%)."""
from benchmark import retention_flops, scoped_trace


def read(params, ctx):
    st = scoped_trace.for_ctx(ctx)
    rows = ctx['counters'].get('state_rows_updated')
    steps = ctx['counters'].get('token_steps')
    if st is None or not rows or not steps:
        return None
    total_ns, ops = st.scope_ns(params['scope'])
    token_steps = len(st.begun(params['per_span'])) \
        * ctx['config']['serve']['decode_span']
    if not ops or not token_steps:
        return None
    model = ctx['config']['model']
    need_ops, need_bytes = retention_flops.decode_update(model)
    updates = token_steps * rows / steps * model['num_layers']
    least_s, _bound = retention_flops.least_seconds(
        need_ops * updates, need_bytes * updates,
        retention_flops.peaks(ctx['device_kind']))
    return 100.0 * least_s / (total_ns / 1e9)
