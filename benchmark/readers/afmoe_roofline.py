"""A share of the roofline for the routed experts' PREFILL product of
the decoder with leading dense layers, over the TRACED part of the
window: the least time the chip could take for the chosen experts'
operations of the true prompt tokens in the ROUTED layers
(benchmark/afmoe_flops.py::experts_prefill, from the program's
counters between the profiler's start and stop: `counters['traced']`)
over the device self time of every op under the scope `scope`, in
percent.  The decode product and the paged kernel count the same for
this model as for the one without dense layers: `routed_roofline`
reads them."""
from benchmark import afmoe_flops as af
from benchmark import scoped_trace


def read(params, ctx):
    st = scoped_trace.for_ctx(ctx)
    traced = ctx['counters'].get('traced')
    if st is None or not traced:
        return None
    total_ns, ops = st.scope_ns(params['scope'])
    if not ops or not total_ns:
        return None
    need = af.experts_prefill(ctx['config']['model'],
                              traced['prefill_tokens'], traced['prefills'])
    least_s, _bound = af.least_seconds(*need, af.peaks(ctx['device_kind']))
    if not least_s:
        return None
    return 100.0 * least_s / (total_ns / 1e9)
