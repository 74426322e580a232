"""What the program's host spans carry, summed over the spans called
`span` that BEGIN inside the traced span: the work of exactly the
dispatches the window holds, counted where the trace records it, with
no host counter read beside the trace (`telemetry.span`'s arguments,
which `serving/engine.py` names and PERF.md section 3 lists beside the
metrics that read them).

Parameters: `span`; `sum` (an argument's name), or `num` and `den`
(each an argument's name or `count`, the number of spans); `scale`.
With `module` (the name of the compiled module a dispatch runs, as the
chip's `XLA Modules` line has it, say `jit_decode_fn`) and `read_by`
(the span in which the host waits for a dispatch or reads it back:
`serve.absorb`, `serve.first_token_sync`) a span counts only where
the module execution of its dispatch lies whole inside the window;
`scope`, a regex, then makes the numerator the device self time in ns
of the ops under that scope inside those same executions, on the first
chip.  So the work and the device time are of one set of dispatches:
the one in flight as the window opens, and the one sent as it closes,
count on neither side.

A dispatch is matched to its execution by order: the chip runs one
module's executions in the order the host sent them, and the
dispatches of one span name are numbered one by one (`dispatch`).
Where the host's reading of dispatch k ends, the execution of k has
ended, and the one after it has not unless it ran in less time than
the host took to see the end of k (a few ms): the offset from number
to execution is the least, over the readings, of the index of the
last execution ended by then less k.

None where the run has no chip trace, or where no such span carries
the arguments named (a program whose spans carry none).  An argument
is a stat of its host event: `jax.profiler.ProfileData` reads it,
where `scoped_trace`'s own descriptor drops it.  The file is read once
a run and kept on the ctx."""
import bisect

from benchmark import reduce_trace, scoped_trace

COUNT = 'count'
MODULES_LINE = 'XLA Modules'


def _read(path):
    """(spans, runs) of the trace at `path`: the program's spans on the
    host plane as [(name, start_ns, end_ns, {argument: value})], and
    the first chip's module executions as {module: [(start_ns,
    end_ns)]}, in the order it ran them."""
    from jax.profiler import ProfileData
    spans, chips = [], {}
    for plane in ProfileData.from_file(path).planes:
        m = reduce_trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULES_LINE:
                runs = chips.setdefault(int(m.group(1)), {})
                for ev in line.events:
                    start = float(ev.start_ns)
                    runs.setdefault(ev.name.split('(')[0], []).append(
                        (start, start + float(ev.duration_ns)))
            elif plane.name == reduce_trace.HOST_PLANE:
                for ev in line.events:
                    if ev.name.startswith(scoped_trace.SPAN_PREFIXES):
                        start = float(ev.start_ns)
                        spans.append((ev.name, start,
                                      start + float(ev.duration_ns),
                                      dict(ev.stats)))
    runs = chips[min(chips)] if chips else {}
    return spans, {k: sorted(v) for k, v in runs.items()}


def _parsed(ctx):
    """(ScopedTrace, spans, runs) of the run's trace, or None."""
    st = scoped_trace.for_ctx(ctx)
    if st is None or st.path is None:
        return None
    parsed = ctx.setdefault('span_args', {})
    if st.path not in parsed:
        parsed[st.path] = _read(st.path)
    return (st, *parsed[st.path])


def spans(ctx, name, read_by=None, module=None):
    """[(args, run)] of the spans called `name` begun inside the window
    of the run's trace, `run` None; with `read_by` and `module` only
    those whose dispatch's execution of `module` lies whole in the
    window, `run` its (start_ns, end_ns) on the first chip.  [] without
    a trace."""
    got = _parsed(ctx)
    if got is None:
        return []
    st, every, runs = got
    lo, hi = st.window() or (0.0, -1.0)
    begun = [(s, args) for n, s, _, args in every
             if n == name and lo <= s <= hi]
    if read_by is None:
        return [(args, None) for _, args in begun]
    runs = runs.get(module, [])
    ends = [e for _, e in runs]
    read = [bisect.bisect_right(ends, e) - 1 - args['dispatch']
            for n, _, e, args in every
            if n == read_by and 'dispatch' in args]
    if not read:
        return []
    offset = min(read)
    out = []
    for sent, args in begun:
        i = args['dispatch'] + offset if 'dispatch' in args else -1
        if 0 <= i < len(runs) and max(lo, sent) <= runs[i][0] \
                and runs[i][1] <= hi:
            out.append((args, runs[i]))
    return out


def scope_ns(ctx, pattern, runs):
    """(self ns, ops) of the first chip's ops under `pattern` (as
    `ScopedTrace.scope_ns`) that begin inside the module executions
    `runs` (sorted)."""
    import re
    st = scoped_trace.for_ctx(ctx)
    ops = st.device_ops[min(st.device_ops)] if st.device_ops else []
    starts = [op[1] for op in ops]
    inside = [i for lo, hi in runs for i in range(
        bisect.bisect_left(starts, lo), bisect.bisect_right(starts, hi))]
    # self_times keys by instruction name: give each op its index as one
    own = reduce_trace.self_times(
        [(f'%{i} = ', ops[i][1], ops[i][2]) for i in inside])
    rx = re.compile(pattern)
    hits = [ns for i, (ns, _) in own.items() if rx.search(ops[int(i)][3])]
    return sum(hits), len(hits)


def read(params, ctx):
    named = [params[k] for k in ('sum', 'num', 'den')
             if params.get(k, COUNT) != COUNT]
    found = [(args, run) for args, run in spans(
                 ctx, params['span'], params.get('read_by'),
                 params.get('module'))
             if all(n in args for n in named)]
    if not found:
        return None

    def total(name):
        return len(found) if name == COUNT \
            else sum(args[name] for args, _ in found)

    if 'sum' in params:
        num, den = total(params['sum']), 1
    else:
        den = total(params['den'])
        if 'scope' in params:
            num, ops = scope_ns(ctx, params['scope'],
                                sorted(run for _, run in found))
            if not ops:
                return None
        else:
            num = total(params['num'])
    if not den:
        return None
    return num / den * params.get('scale', 1.0)
