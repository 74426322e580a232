"""Share of the first chip's busy time inside the traced span that is
self time of the ops whose op_name carries `scope` (a regex, searched
along the whole path), in percent; with `unscoped`, of the ops that
carry none of the program's scopes and are no Pallas kernel.  A program
that names no scope at all has no share to report."""
from benchmark import scoped_trace


def read(params, ctx):
    st = scoped_trace.for_ctx(ctx)
    if st is None or not st.busy_ns() or not st.names_scopes():
        return None
    if params.get('unscoped'):
        part_ns = st.unscoped_ns()
    else:
        part_ns, ops = st.scope_ns(params['scope'])
        if not ops:
            return None
    return 100.0 * part_ns / st.busy_ns()
