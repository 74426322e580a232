"""Mean length, in milliseconds, of the program's host spans called
`span` that lie whole inside the traced span, each less the spans
inside it whose name is in `less` (the waits for the device, where the
host's own time is wanted)."""
from benchmark import scoped_trace


def read(params, ctx):
    st = scoped_trace.for_ctx(ctx)
    if st is None:
        return None
    less = tuple(params.get('less', ()))
    own = [e - s - sum(ce - cs for n, cs, ce in st.children((name, s, e))
                       if n in less)
           for name, s, e in st.spans(params['span'])]
    if not own:
        return None
    return sum(own) / len(own) / 1e6
