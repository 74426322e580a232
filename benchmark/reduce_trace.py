"""Reduce a jax profiler trace (.xplane.pb) to the benchmark's numbers.

What a chip's trace holds (looked at by hand, PR 24): one plane per
chip, '/device:TPU:<n>', whose line 'XLA Ops' carries one event per
executed HLO instruction; and '/host:CPU', one line per host thread,
where jax.profiler.TraceAnnotation spans and jax's own calls land.
Both are on one clock, in nanoseconds.

Events on 'XLA Ops' NEST: a `while` spans the fusions of its body.  So
busy time is a union of intervals and never a sum, and the per-name
table is of SELF time (an event's duration less its children's), which
does add up to the union.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
HOST_PLANE = '/host:CPU'
TRACED_SPAN = 'bench.traced'


def find_xplane(trace_dir):
    """The newest .xplane.pb the profiler wrote under trace_dir."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    return found[-1] if found else None


def short_name(name):
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'.  A Pallas
    kernel's instruction is named after the kernel
    ('%transpose_jvp_flash_bwd_dkv__.9'), so its name survives."""
    head = name.split(' = ', 1)[0].strip()
    return head.lstrip('%')[:80]


def stem(short):
    """'jvp_flash_fwd_.12' -> 'jvp_flash_fwd_': the same instruction of
    another layer counts under one name (and every fusion XLA left
    unnamed under 'fusion')."""
    head, _, tail = short.rpartition('.')
    return head if head and tail.isdigit() else short


class Trace:
    """Device op events per chip and host events per thread, as
    (name, start_ns, end_ns) tuples sorted by start."""

    def __init__(self, device_ops, host_lines):
        self.device_ops = device_ops      # {chip: [(name, s, e)]}
        self.host_lines = host_lines      # {thread line: [(name, s, e)]}

    @classmethod
    def from_file(cls, path):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        device_ops, host_lines = {}, {}
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        device_ops[int(m.group(1))] = _events(line)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    host_lines[line.name] = _events(line)
        return cls(device_ops, host_lines)

    def host_span(self, name):
        """(start, end) over every host event called `name`."""
        hits = [(s, e) for evs in self.host_lines.values()
                for n, s, e in evs if n == name]
        if not hits:
            return None
        return min(s for s, _ in hits), max(e for _, e in hits)

    def kernel(self, pattern):
        """(total ns, calls) inside the traced span, on the first chip,
        of the ops whose short name matches `pattern`."""
        if not self.device_ops:
            return 0.0, 0
        ops = clip(self.device_ops[min(self.device_ops)], self.window())
        return kernel_ns(ops, pattern)

    def window(self):
        """The traced span: the harness's 'bench.traced' annotation,
        or, where there is none, first to last device op."""
        span = self.host_span(TRACED_SPAN)
        if span is not None:
            return span
        evs = [ev for ops in self.device_ops.values() for ev in ops]
        if not evs:
            return None
        return min(s for _, s, _ in evs), max(e for _, _, e in evs)


def _events(line):
    out = [(ev.name, float(ev.start_ns),
            float(ev.start_ns) + float(ev.duration_ns))
           for ev in line.events]
    out.sort(key=lambda t: (t[1], -t[2]))
    return out


def clip(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events):
    """Merged [start, end] intervals of (name, start, end) events."""
    merged = []
    for _, s, e in sorted(events, key=lambda t: t[1]):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def busy_ns(events):
    return sum(e - s for s, e in union(events))


def self_times(events):
    """{short name: [self ns, calls]} — an event's duration less the
    events nested inside it, so the values add up to busy_ns."""
    out = {}
    stack = []      # [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            slot = out.setdefault(short_name(name), [0.0, 0])
            slot[0] += max(own, 0.0)
            slot[1] += 1

    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    close(float('inf'))
    return out


def kernel_ns(events, pattern):
    """(total ns, calls) of the events whose short name matches
    `pattern` (a regex, searched).  Kernels do not nest in each other,
    so this is a sum."""
    rx = re.compile(pattern)
    hits = [e - s for n, s, e in events if rx.search(short_name(n))]
    return sum(hits), len(hits)


def idle_gaps(device_events, host_events, window):
    """{host span name: idle ns}: each gap of the device inside the
    window, charged to the innermost host event that covers the gap's
    middle ('(no host span)' where none does)."""
    lo, hi = window
    gaps, at = [], lo
    for s, e in union(clip(device_events, window)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    out = {}
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(he - hs, n) for n, hs, he in host_events
                 if hs <= mid <= he]
        name = min(cover)[1] if cover else '(no host span)'
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def main_host_line(trace):
    """The host thread that carries the harness's spans."""
    for name, evs in trace.host_lines.items():
        if any(n.startswith('bench.') for n, _, _ in evs):
            return evs
    return []


def summary(trace, top=10):
    """busy_s / window_s averaged over chips, and the breakdown the
    result line carries."""
    window = trace.window()
    if window is None or not trace.device_ops:
        return None
    chips = sorted(trace.device_ops)
    busy = [busy_ns(clip(trace.device_ops[c], window)) for c in chips]
    first = clip(trace.device_ops[chips[0]], window)
    by_stem = {}
    for name, (ns, _calls) in self_times(first).items():
        by_stem[stem(name)] = by_stem.get(stem(name), 0.0) + ns
    ops = sorted(((ns, n) for n, ns in by_stem.items()),
                 reverse=True)[:top]
    gaps = sorted(((ns, n) for n, ns in idle_gaps(
        trace.device_ops[chips[0]], main_host_line(trace),
        window).items()), reverse=True)[:top]
    return {
        'busy_s': sum(busy) / len(busy) / 1e9,
        'window_s': (window[1] - window[0]) / 1e9,
        'breakdown': {
            'device_ops': [[n, ns / 1e9] for ns, n in ops],
            'idle_gaps': [[n, ns / 1e9] for ns, n in gaps]},
    }
