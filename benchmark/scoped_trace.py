"""Read a profiler trace (.xplane.pb) with what reduce_trace.Trace
drops: the scope of each device op and the nesting of the program's own
host spans.

What the trace holds (looked at by hand, PR 25): an event of a chip's
'XLA Ops' line points at an XEventMetadata, and that metadata carries a
stat `tf_op`: the instruction's `op_name` from the compiled module, the
path of jax name scopes down to the primitive
('jit(decode_fn)/serve.decode/while/body/closed_call/gpt.attn/'
'paged.attention/paged.gather_dense/gather:', and backward
'jit(train_step)/transpose(jvp(gpt.mlp))/dot_general:').  A fusion has
the op_name of ONE of the instructions it swallowed.
jax.profiler.ProfileData yields an event's own stats only, not its
metadata's, so this file parses the protobuf itself (google.protobuf,
from a descriptor of the few fields it reads).  The program's host spans
(`telemetry.span`: 'serve.*', 'trainer.*') and the harness's ('bench.*')
are TraceAnnotations on the host line of the same file, on one clock.

Everything is clipped to the harness's 'bench.traced' span, and all
device numbers are of the first chip, as in reduce_trace.  Three tables:
(a) device self time by innermost program scope, which adds up to the
window's busy time; (b) host self time by span; (c) device idle time by
the innermost program span that covers it, which adds up to window less
busy.

    python3 -m benchmark.scoped_trace <file.xplane.pb>
"""
import functools
import glob
import os
import re
import sys

from benchmark import harness, reduce_trace

# the program's device scopes (jax.named_scope) and host span prefixes
PROGRAM_SCOPES = (
    'paged.write_kv', 'paged.gather_dense', 'paged.attention',
    'fused_ce.fwd', 'fused_ce.bwd', 'optimizer_update',
    'serve.prefill', 'serve.decode', 'serve.sample',
    'gpt.embed', 'gpt.attn', 'gpt.mlp', 'gpt.ln')
SPAN_PREFIXES = ('serve.', 'trainer.')
HARNESS_PREFIX = 'bench.'
STEP_SPANS = ('serve.step', 'trainer.step')
NO_SPAN = '(no span)'

_SCOPE_RX = re.compile('|'.join(re.escape(s) for s in PROGRAM_SCOPES))
_XSPACE = None


def _xspace_class():
    """The message class of an XSpace, from a descriptor of the fields
    read here (tsl/profiler/protobuf/xplane.proto; a map is on the wire
    a repeated entry of key 1 and value 2)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    field = descriptor_pb2.FieldDescriptorProto
    kinds = {'int64': field.TYPE_INT64, 'uint64': field.TYPE_UINT64,
             'string': field.TYPE_STRING}
    schema = {
        'XSpace': [('planes', 1, '*XPlane')],
        'XPlane': [('name', 2, 'string'), ('lines', 3, '*XLine'),
                   ('event_metadata', 4, '*EventEntry'),
                   ('stat_metadata', 5, '*StatEntry')],
        'EventEntry': [('key', 1, 'int64'), ('value', 2, 'XEventMetadata')],
        'StatEntry': [('key', 1, 'int64'), ('value', 2, 'XStatMetadata')],
        'XLine': [('name', 2, 'string'), ('timestamp_ns', 3, 'int64'),
                  ('events', 4, '*XEvent')],
        'XEvent': [('metadata_id', 1, 'int64'), ('offset_ps', 2, 'int64'),
                   ('duration_ps', 3, 'int64')],
        'XEventMetadata': [('name', 2, 'string'), ('stats', 5, '*XStat')],
        'XStat': [('metadata_id', 1, 'int64'), ('str_value', 5, 'string'),
                  ('ref_value', 7, 'uint64')],
        'XStatMetadata': [('name', 2, 'string')],
    }
    package = 'bench_scoped_trace'
    proto = descriptor_pb2.FileDescriptorProto(
        name=package + '.proto', package=package, syntax='proto3')
    for name, fields in schema.items():
        message = proto.message_type.add(name=name)
        for fname, number, kind in fields:
            f = message.field.add(
                name=fname, number=number,
                label=field.LABEL_REPEATED if kind[0] == '*'
                else field.LABEL_OPTIONAL)
            kind = kind.lstrip('*')
            if kind in kinds:
                f.type = kinds[kind]
            else:
                f.type = field.TYPE_MESSAGE
                f.type_name = f'.{package}.{kind}'
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName(package + '.XSpace'))
    return _XSPACE


@functools.lru_cache(maxsize=None)      # a few thousand distinct paths
def innermost_scope(op_name):
    """The last of the program's scopes on an op's path, or None."""
    found = _SCOPE_RX.findall(op_name)
    return found[-1] if found else None


def is_pallas(op_name):
    return 'pallas_call' in op_name


def innermost_segments(spans):
    """[(start, end, name)]: which of the nested spans is innermost at
    each moment that any covers."""
    out, stack, at = [], [], 0.0

    def advance(upto):
        nonlocal at
        if stack and upto > at:
            out.append((at, upto, stack[-1][0]))
        at = max(at, upto)

    for name, s, e in sorted(spans, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((name, e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return out


class ScopedTrace:
    """Device ops per chip as (name, start_ns, end_ns, op_name) and the
    spans of the host line that carries them as (name, start_ns,
    end_ns), both sorted by start."""

    def __init__(self, device_ops, host_spans, path=None):
        self.device_ops = device_ops
        self.host_spans = host_spans
        self.path = path
        self._ops = None

    @classmethod
    def from_file(cls, path):
        space = _xspace_class()()
        with open(path, 'rb') as f:
            space.ParseFromString(f.read())
        device_ops, lines = {}, []
        for plane in space.planes:
            m = reduce_trace.DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == reduce_trace.OPS_LINE:
                        device_ops[int(m.group(1))] = _device_ops(
                            plane, line)
            elif plane.name == reduce_trace.HOST_PLANE:
                names = {e.key: e.value.name for e in plane.event_metadata}
                lines += [_host_spans(names, line) for line in plane.lines]
        # the line of the harness's traced span, else the one with the
        # most spans (a session the harness did not open)
        main = max(lines, default=[], key=lambda evs: (
            any(n == reduce_trace.TRACED_SPAN for n, _, _ in evs),
            len(evs)))
        return cls(device_ops, main, path)

    def window(self):
        """As reduce_trace.Trace.window: the 'bench.traced' span, else
        first to last device op."""
        hits = [(s, e) for n, s, e in self.host_spans
                if n == reduce_trace.TRACED_SPAN]
        if hits:
            return min(s for s, _ in hits), max(e for _, e in hits)
        evs = [ev for ops in self.device_ops.values() for ev in ops]
        if not evs:
            return None
        return min(ev[1] for ev in evs), max(ev[2] for ev in evs)

    def ops(self):
        """[(name, op_name, self ns)] of the first chip's ops inside the
        window: self time as reduce_trace.self_times has it."""
        if self._ops is None:
            self._ops = []
            window = self.window()
            if window is not None and self.device_ops:
                ops = self.device_ops[min(self.device_ops)]
                # self_times keys by instruction name: give each event
                # its index as one
                own = reduce_trace.self_times(reduce_trace.clip(
                    [(f'%{i} = ', s, e)
                     for i, (_, s, e, _) in enumerate(ops)], window))
                self._ops = [(ops[int(i)][0], ops[int(i)][3], ns)
                             for i, (ns, _) in own.items()]
        return self._ops

    def busy_ns(self):
        return sum(ns for _, _, ns in self.ops())

    def idle_ns(self):
        window = self.window()
        return (window[1] - window[0] - self.busy_ns()) if window else 0.0

    def names_scopes(self):
        """Whether any op in the window carries a program scope: a
        program without them (the parent of PR 25) has no share to
        report, scoped or unscoped."""
        return any(innermost_scope(op) for _, op, _ in self.ops())

    def scope_ns(self, pattern):
        """(self ns, ops) of the ops whose op_name matches `pattern`
        anywhere on its path: forward, backward and inner scopes count
        under an outer one."""
        rx = re.compile(pattern)
        hits = [ns for _, op, ns in self.ops() if rx.search(op)]
        return sum(hits), len(hits)

    def unscoped_ns(self):
        """Self ns of the ops that carry none of the program's scopes
        and are no Pallas kernel."""
        return sum(ns for _, op, ns in self.ops()
                   if innermost_scope(op) is None and not is_pallas(op))

    def by_scope(self):
        """{row: self ns}: the innermost program scope of each op; an
        op with none under '(no scope) <instruction stem>'.  Adds up to
        busy_ns."""
        out = {}
        for name, op, ns in self.ops():
            row = innermost_scope(op) or '(no scope) ' + reduce_trace.stem(
                reduce_trace.short_name(name))
            out[row] = out.get(row, 0.0) + ns
        return out

    def spans(self, name=None):
        """The program's and the harness's spans that lie whole inside
        the window; `name` picks one name."""
        window = self.window()
        if window is None:
            return []
        return [(n, s, e) for n, s, e in self.host_spans
                if s >= window[0] and e <= window[1]
                and n != reduce_trace.TRACED_SPAN
                and (name is None or n == name)]

    def begun(self, name):
        """The spans called `name` that begin inside the window."""
        lo, hi = self.window() or (0.0, -1.0)
        return [(n, s, e) for n, s, e in self.host_spans
                if n == name and lo <= s <= hi]

    def children(self, span):
        """The program's spans inside `span` (a tuple of spans())."""
        _, lo, hi = span
        return [(n, s, e) for n, s, e in self.spans()
                if n.startswith(SPAN_PREFIXES) and lo <= s and e <= hi
                and (n, s, e) != span]

    def span_self(self):
        """{span name: [self ns, count]} over spans()."""
        return reduce_trace.self_times(self.spans())

    def idle_by_span(self):
        """{row: idle ns}: each moment the device is idle inside the
        window under the innermost program span that covers it; outside
        every program span under the harness's span in brackets, else
        NO_SPAN.  Adds up to idle_ns."""
        window = self.window()
        if window is None or not self.device_ops:
            return {}
        ops = self.device_ops[min(self.device_ops)]
        gaps, at = [], window[0]
        for s, e in reduce_trace.union(reduce_trace.clip(
                [ev[:3] for ev in ops], window)):
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if window[1] > at:
            gaps.append((at, window[1]))
        spans = [t for t in self.host_spans
                 if t[0] != reduce_trace.TRACED_SPAN]
        program = innermost_segments(
            [t for t in spans if t[0].startswith(SPAN_PREFIXES)])
        segments = program + _cut_out(
            [(s, e, f'({n})') for s, e, n in innermost_segments(
                [t for t in spans if t[0].startswith(HARNESS_PREFIX)])],
            [(s, e) for s, e, _ in program])
        out = {}
        for lo, hi in gaps:
            left = hi - lo
            for s, e, name in segments:
                cut = min(e, hi) - max(s, lo)
                if cut > 0:
                    out[name] = out.get(name, 0.0) + cut
                    left -= cut
            if left > 0:
                out[NO_SPAN] = out.get(NO_SPAN, 0.0) + left
        return out


def _cut_out(segments, holes):
    """`segments` less the intervals `holes` (sorted, disjoint)."""
    out = []
    for s, e, name in segments:
        at = s
        for lo, hi in holes:
            if hi <= at or lo >= e:
                continue
            if lo > at:
                out.append((at, lo, name))
            at = max(at, hi)
        if e > at:
            out.append((at, e, name))
    return out


def _extent(line, ev):
    """(start, end) in whole nanoseconds, as ProfileData's start_ns and
    duration_ns, which reduce_trace reads, have them."""
    start = float(line.timestamp_ns + ev.offset_ps // 1000)
    return start, start + float(ev.duration_ps // 1000)


def _device_ops(plane, line):
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {}
    for entry in plane.event_metadata:
        op_name = ''
        for stat in entry.value.stats:
            if stat_names.get(stat.metadata_id) == 'tf_op':
                op_name = stat.str_value \
                    or stat_names.get(stat.ref_value, '')
        meta[entry.key] = (entry.value.name, op_name)
    out = []
    for ev in line.events:
        name, op_name = meta.get(ev.metadata_id, ('', ''))
        out.append((name, *_extent(line, ev), op_name))
    out.sort(key=lambda t: (t[1], -t[2]))
    return out


def _host_spans(names, line):
    out = []
    for ev in line.events:
        name = names.get(ev.metadata_id, '')
        if name.startswith(SPAN_PREFIXES + (HARNESS_PREFIX,)):
            out.append((name, *_extent(line, ev)))
    out.sort(key=lambda t: (t[1], -t[2]))
    return out


def newest_xplane():
    """The newest trace any cell of this checkout wrote: one process
    runs one cell, and a reader's ctx names no path."""
    found = glob.glob(os.path.join(
        harness.OUT_DIR, 'trace', '*', 'plugins', 'profile', '*',
        '*.xplane.pb'))
    return max(found, key=os.path.getmtime) if found else None


def for_ctx(ctx):
    """The ScopedTrace of the run a reader's ctx belongs to, read once
    and kept on the ctx; None where the run has no chip trace.  The
    first call prints the three tables to stderr."""
    if 'scoped_trace' not in ctx:
        path = newest_xplane() if ctx.get('trace') is not None else None
        ctx['scoped_trace'] = st = \
            ScopedTrace.from_file(path) if path else None
        if st is not None:
            print(tables(st), file=sys.stderr, flush=True)
    return ctx['scoped_trace']


def step_cover(st):
    """(least, mean) share of a step span's length that its children
    cover, over the whole step spans of the window; None without."""
    shares = []
    for span in st.spans():
        if span[0] in STEP_SPANS and span[2] > span[1]:
            shares.append(sum(e - s for _, s, e in st.children(span))
                          / (span[2] - span[1]))
    return (min(shares), sum(shares) / len(shares)) if shares else None


def idle_inside_children(st):
    """Share of the window's device idle time inside a program span
    other than the step spans themselves."""
    idle = st.idle_by_span()
    total = sum(idle.values())
    inside = sum(ns for name, ns in idle.items()
                 if name.startswith(SPAN_PREFIXES)
                 and name not in STEP_SPANS)
    return inside / total if total else None


def tables(st, top=24):
    window = st.window()
    if window is None:
        return '[scoped_trace] no window'
    busy, idle = st.busy_ns(), st.idle_ns()
    rows = [f'[scoped_trace] {st.path}: window '
            f'{(window[1] - window[0]) / 1e6:.3f} ms, device busy '
            f'{busy / 1e6:.3f} ms, idle {idle / 1e6:.3f} ms']

    def table(title, items, total):
        rows.append(f'[scoped_trace] {title}')
        items = sorted(items, key=lambda kv: -kv[1])
        for name, ns in items[:top]:
            rows.append(f'  {ns / 1e6:12.3f} ms {100 * ns / total:6.2f}%'
                        f'  {name}' if total else f'  {name}')
        rest = sum(ns for _, ns in items[top:])
        if rest:
            rows.append(f'  {rest / 1e6:12.3f} ms {100 * rest / total:6.2f}%'
                        f'  ({len(items) - top} more rows)')
        rows.append(f'  {sum(ns for _, ns in items) / 1e6:12.3f} ms total')

    table('device self time by innermost program scope (share of busy)',
          st.by_scope().items(), busy)
    span_self = st.span_self()
    table('host self time by span (share of the window), calls: '
          + ', '.join(f'{n} {c}' for n, (_, c) in sorted(span_self.items())),
          [(n, ns) for n, (ns, _) in span_self.items()],
          window[1] - window[0])
    table('device idle time by innermost program span (share of idle)',
          st.idle_by_span().items(), idle)
    cover = step_cover(st)
    if cover is not None:
        rows.append(f'[scoped_trace] children cover of a step span: least '
                    f'{100 * cover[0]:.2f}%, mean {100 * cover[1]:.2f}%')
    inside = idle_inside_children(st)
    if inside is not None:
        rows.append(f'[scoped_trace] idle time inside a program span other '
                    f'than the step spans: {100 * inside:.2f}%')
    return '\n'.join(rows)


if __name__ == '__main__':
    print(tables(ScopedTrace.from_file(sys.argv[1])))
