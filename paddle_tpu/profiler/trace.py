"""On-device trace parsing: perfetto ``*.trace.json.gz`` → per-op
durations, with a census join against the compiled HLO module.

``jax.profiler.start_trace`` / ``stop_trace`` emit a gzip'd Chrome/
perfetto trace under ``<logdir>/plugins/profile/<run>/`` — on TPU the
device timeline threads carry one event per executed HLO instruction;
on the CPU backend the thunk executor annotates every instruction the
same way (one event per device per execution, named exactly like the
instruction in the compiled module text: ``all-reduce``, ``dot.1``,
``broadcast_multiply_fusion``).  That name identity is the whole
trick: a profiled collective joins the ``analysis.hlo`` census by
**instruction name**, which carries its opcode, operand bytes and
replica-group — so observed microseconds meet predicted wire bytes
and phases with no side channel.

Stdlib-only parsing (gzip + json — no tensorflow/tensorboard import):
this must run inside the training process at a profile-window close
and on a dev machine against an archived trace.

    prof = parse_trace('…/host.trace.json.gz')
    idx  = analysis.hlo.collective_instrs(module, mesh_shape=…)
    rows = match_collectives(prof, idx, num_partitions=8)
    # rows are ready to emit as ``collective_observed`` events

``telemetry.profile.StepProfiler`` drives exactly this pipeline on a
sampled schedule.
"""
import glob
import gzip
import json
import os
import re

__all__ = ['find_traces', 'parse_trace', 'TraceProfile',
           'match_collectives', 'collective_base', 'is_op_event_name']

# collective base opcodes (mirrors analysis.costmodel.COLLECTIVE_OPS;
# kept literal so this module imports nothing from the package and
# stays usable on a bare dev machine)
COLLECTIVE_OPS = ('all-reduce', 'all-gather', 'reduce-scatter',
                  'all-to-all', 'collective-permute')

# an XLA instruction name: lowercase opcode root plus dotted/dashed
# suffixes ('fusion.3', 'all-reduce-start.1', 'dot_general');
# runtime/infra annotations carry '::', '(', spaces, '$' or a
# CamelCase head ('ParseArguments') — instruction names never do
_OP_NAME_RE = re.compile(r'^[a-z_][\w.\-]*$')
# infra events that match the name shape anyway (seen on the CPU
# thunk runtime); anything here is host bookkeeping, not device work
_INFRA_NAMES = frozenset((
    'ParseArguments', 'CopyToDevice', 'CopyFromDevice', 'Execute',
    'ExecuteHelper', 'BufferFromHostBuffer', 'ToLiteral',
))
_SUFFIX_RE = re.compile(r'\.\d+$')


def is_op_event_name(name):
    """True when a trace event name looks like an executed HLO
    instruction (vs runtime scaffolding)."""
    if not name or name in _INFRA_NAMES:
        return False
    return bool(_OP_NAME_RE.match(name))


def collective_base(name):
    """Base collective opcode of an instruction name, or None.
    ``all-reduce-start.1`` → ``all-reduce``."""
    root = _SUFFIX_RE.sub('', name)
    for suffix in ('-start', '-done'):
        if root.endswith(suffix):
            root = root[:-len(suffix)]
    return root if root in COLLECTIVE_OPS else None


def _done_half(name):
    """True for the '-done' half of an async pair: its duration is
    the WAIT, already covered by the '-start' op's transfer time —
    totals that summed both would double-count one collective."""
    return _SUFFIX_RE.sub('', name).endswith('-done')


def find_traces(logdir):
    """All ``*.trace.json.gz`` under `logdir`, oldest → newest (one
    per host per capture; jax nests them under plugins/profile/<run>)."""
    found = glob.glob(os.path.join(logdir, '**', '*.trace.json.gz'),
                      recursive=True)     # '**' is also no directory
    return sorted(found, key=lambda f: (os.path.getmtime(f), f))


class TraceProfile:
    """Aggregated per-op view of one captured trace.

    ``ops`` maps instruction name → {count, total_us, avg_us}; counts
    include every device's execution of every step inside the window
    (8 devices × 3 steps → count 24).  ``device_total_us`` /
    ``collective_total_us`` sum all op events — divide by
    (devices × steps) for a per-step-per-device figure.
    """

    __slots__ = ('ops', 'n_events', 'device_total_us',
                 'collective_total_us', 'source', 'device_pids')

    def __init__(self, ops, n_events=0, device_pids=0, source=None):
        self.ops = ops
        self.n_events = n_events
        self.device_pids = device_pids
        self.source = source
        self.device_total_us = sum(r['total_us'] for r in ops.values())
        self.collective_total_us = sum(
            r['total_us'] for r in ops.values()
            if collective_base(r['name'])
            and not _done_half(r['name']))

    def collectives(self):
        """The collective op rows, keyed by instruction name."""
        return {n: r for n, r in self.ops.items()
                if collective_base(n)}

    def top(self, k=20):
        return sorted(self.ops.values(),
                      key=lambda r: r['total_us'], reverse=True)[:k]

    def summary(self):
        return {'n_ops': len(self.ops), 'n_events': self.n_events,
                'device_total_us': round(self.device_total_us, 3),
                'collective_total_us': round(
                    self.collective_total_us, 3),
                'source': self.source}


def _load_doc(path_or_doc):
    if isinstance(path_or_doc, dict):
        return path_or_doc, None
    path = path_or_doc
    opener = gzip.open if path.endswith('.gz') else open
    with opener(path, 'rt') as fh:
        return json.load(fh), path


def parse_trace(path_or_doc):
    """Parse one trace (a ``*.trace.json[.gz]`` path or an already-
    loaded dict) into a :class:`TraceProfile`.

    Device selection: when the trace carries device processes
    (``process_name`` metadata containing ``/device:`` — the TPU/GPU
    layout), only events on those pids count as op events; otherwise
    (CPU thunk runtime: one ``/host:CPU`` process whose worker threads
    run the thunks) every complete event whose name has the
    instruction shape counts.
    """
    doc, path = _load_doc(path_or_doc)
    events = doc.get('traceEvents', [])
    device_pids = set()
    for e in events:
        if e.get('ph') == 'M' and e.get('name') == 'process_name':
            pname = (e.get('args') or {}).get('name', '')
            if '/device:' in pname:
                device_pids.add(e.get('pid'))
    ops = {}
    n = 0
    for e in events:
        if e.get('ph') != 'X':
            continue
        name = e.get('name')
        if device_pids and e.get('pid') not in device_pids:
            continue
        if not is_op_event_name(name):
            continue
        dur = e.get('dur')
        if dur is None:
            continue
        row = ops.setdefault(name, {'name': name, 'count': 0,
                                    'total_us': 0.0})
        row['count'] += 1
        row['total_us'] += float(dur)
        n += 1
    for row in ops.values():
        row['total_us'] = round(row['total_us'], 3)
        row['avg_us'] = round(row['total_us'] / row['count'], 3)
    return TraceProfile(ops, n_events=n, device_pids=len(device_pids),
                        source=path)


def match_collectives(profile, instr_index, *, num_partitions=1,
                      name=None):
    """Join a trace profile against the compiled module's collective
    census index (``analysis.hlo.collective_instrs``).

    For each census instruction, the trace row of the same name (or
    its async ``-start`` twin — the start op carries the transfer
    time) yields observed per-call microseconds: the trace counts one
    event per device per execution, so ``us = total / count`` is the
    per-call, per-device duration and ``calls = count / devices`` the
    executions inside the window.  Returns rows shaped for
    ``collective_observed`` telemetry events: op, instr, us,
    wire_bytes, phases, calls, bytes, group_size, axes, predicted_us.

    Census instructions the trace never timed (elided by the backend)
    are skipped; trace collectives with no census row (no HLO text in
    hand) are NOT emitted — without bytes they cannot feed the
    calibration fit.
    """
    rows = []
    per_dev = max(1, int(num_partitions or 1))
    for iname, info in instr_index.items():
        # the census disambiguates cross-computation name collisions
        # as 'name@computation'; the trace knows only the bare name
        tname = iname.split('@', 1)[0]
        row = profile.ops.get(tname)
        if row is None:
            # async pair: census keys the '-start' op already, but a
            # backend may time the bare name (or vice versa).  The
            # numeric suffix stays OUTSIDE the toggle:
            # 'all-reduce-start.1' <-> 'all-reduce.1'
            m = _SUFFIX_RE.search(tname)
            root, suffix = (tname[:m.start()], m.group(0)) if m \
                else (tname, '')
            alt_root = root[:-len('-start')] \
                if root.endswith('-start') else root + '-start'
            row = profile.ops.get(alt_root + suffix)
        if row is None or not row['count']:
            continue
        calls = max(1, row['count'] // per_dev)
        out = {'op': info['op'], 'instr': iname,
               'us': round(row['total_us'] / row['count'], 3),
               'calls': calls,
               'wire_bytes': info['wire_bytes'],
               'phases': info['phases'],
               'bytes': info['bytes'],
               'group_size': info['group_size'],
               'axes': [list(a) for a in info.get('axes') or ()],
               'wire_dtype': info.get('wire_dtype'),
               'predicted_us': info.get('est_us')}
        if name:
            out['name'] = name
        rows.append(out)
    return rows
