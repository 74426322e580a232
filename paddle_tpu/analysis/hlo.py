"""Lowered-HLO SPMD audit — what the partitioner DID, not what the
trace asked for.

The jaxpr rules (``analysis.rules``) see the program before XLA's
GSPMD partitioner runs, so three first-order TPU costs are invisible
to them: where reshards/collectives actually land, how many bytes they
move, and which intermediates end up materialized at FULL size on
every device.  This module lowers a step through ``jax.jit(...)
.lower().compile()`` — abstract shapes only, no device execution, and
it works under ``JAX_PLATFORMS=cpu`` with a forced
``--xla_force_host_platform_device_count`` mesh — then parses the
compiled (post-partitioner, per-device, scheduled) HLO text into a
lightweight op graph and runs a second rule registry over it:

``replicated-giant-hlo``  per-device buffers at the FULL global shape
                          of a traced intermediate: the partitioner
                          left them replicated (catches input-derived
                          values the jaxpr const-dataflow rule cannot)
``collective-cost``       census of all-reduce / all-gather /
                          reduce-scatter / all-to-all /
                          collective-permute with per-op byte counts
                          and a ring latency+bandwidth estimate
                          (``analysis.costmodel``); flags oversized
                          collectives and all-gathers feeding only
                          elementwise consumers (could run sharded)
``resharding``            all-to-all ops the partitioner inserted
                          because adjacent shardings conflict
``peak-memory``           liveness walk over the scheduled entry
                          computation: per-device high-water estimate
                          against a configurable HBM budget

Entry points: ``audit`` (lower a callable), ``audit_text`` (a compiled
HLO module already in hand — ParallelTrainer reuses its census text).
Reports are ordinary ``analysis.LintReport``s (findings carry
``origin='hlo'`` and the source location from HLO metadata, so
``# tpu-lint: disable=`` suppressions apply) with an ``extras`` dict
(collective census, predicted cost, peak memory) that
``tools/tpu_lint.py --hlo`` and the ``collective_cost`` telemetry
event surface.
"""
import math
import re

from . import costmodel
from .findings import Finding, LintReport, HIGH, WARN, INFO
from .rules import DEFAULT_THRESHOLDS as _JAXPR_THRESHOLDS

__all__ = ['parse_module', 'HloModule', 'HloComputation', 'HloInstr',
           'buffer_bytes', 'collective_census', 'collective_instrs',
           'peak_memory',
           'HLO_RULES', 'register_hlo_rule', 'HloRuleContext',
           'run_hlo_rules', 'DEFAULT_HLO_THRESHOLDS', 'audit',
           'audit_text', 'auto_shardings', 'lower_text']

DEFAULT_HLO_THRESHOLDS = {
    # replicated-giant-hlo: per-device bytes of an intermediate still
    # at its full traced shape after partitioning (same bar as the
    # jaxpr rule: the two are one diagnosis at two compile stages)
    'replicated_bytes': _JAXPR_THRESHOLDS['replicated_bytes'],
    # collective-cost: wire bytes of ONE collective worth flagging
    'collective_wire_warn': 64 << 20,
    'collective_wire_high': 1 << 30,
    # peak-memory: per-device HBM budget (v5e-class default; real runs
    # pass the chip's budget via thresholds / tpu_lint --hbm-gb)
    'hbm_bytes': 16 << 30,
    'hbm_warn_frac': 0.8,
    # cost-model knobs (costmodel defaults; exposed for A/B vs chips)
    'link_bw_gbps': costmodel.DEFAULT_LINK_BW_GBPS,
    'link_latency_us': costmodel.DEFAULT_LINK_LATENCY_US,
    # optional costmodel.Calibration (measured alpha/beta per op kind,
    # from tools/calibrate_costmodel.py) — overrides the analytic
    # estimate in the census and everything built on it (the planner)
    'calibration': None,
}

_DTYPE_BYTES = {
    'f64': 8, 'f32': 4, 'f16': 2, 'bf16': 2, 'f8e4m3fn': 1,
    'f8e5m2': 1, 's64': 8, 's32': 4, 's16': 2, 's8': 1, 'u64': 8,
    'u32': 4, 'u16': 2, 'u8': 1, 'pred': 1, 'c64': 8, 'c128': 16,
}

# `%name = f32[8,128]{1,0} opcode(...)` / tuple-typed
# `%name = (f32[2]{0}, s32[]{:T(128)}) opcode(...)`; TPU tuple layouts
# nest parens, hence the inner group (same shape as profiler's parser)
_INSTR_RE = re.compile(
    r'^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*'
    r'(\((?:[^()]|\([^()]*\))*\)|\S+)\s+([\w\-]+)\(')
_BUF_RE = re.compile(r'(\w+)\[([\d,]*)\]')
# computation header: `ENTRY %main (...) -> ... {` / `%body.12 (...) {`
_COMP_RE = re.compile(r'^(ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)[^{]*{')
# source metadata: instructions carry `stack_frame_id=N`; the module
# header's FileNames / FileLocations / StackFrames tables resolve a
# frame to the (file, line) of the user code that emitted it
_FRAME_ID_RE = re.compile(r'stack_frame_id=(\d+)')
_TABLE_ROW_RE = re.compile(r'^(\d+)\s+(.*)$')
_TABLE_FIELD_RE = re.compile(r'(\w+)=(\d+)')
# iota replica groups: `replica_groups=[8,2]<=[16]` (groups x size)
_GROUPS_IOTA_RE = re.compile(r'replica_groups=\[(\d+),(\d+)\]<=')
_GROUPS_LIST_RE = re.compile(r'replica_groups=\{\{([\d,]*)\}')
_CALLED_RE = re.compile(
    r'(?:calls|to_apply|body|condition|true_computation|'
    r'false_computation|branch_computations)='
    r'(\{[^}]*\}|%[\w.\-]+)')
_NUM_PARTITIONS_RE = re.compile(r'num_partitions=(\d+)')
_OPERAND_NAME_RE = re.compile(r'%([\w.\-]+)')

# ops whose "output" aliases/repackages an existing buffer — no new
# HBM allocation worth accounting
_ALIAS_OPS = frozenset((
    'parameter', 'tuple', 'get-tuple-element', 'bitcast'))

# elementwise consumers an all-gather could have run sharded through
# (kLoop fusions count: their bodies are elementwise by construction)
_ELEMENTWISE_OPS = frozenset((
    'add', 'subtract', 'multiply', 'divide', 'maximum', 'minimum',
    'power', 'exponential', 'exponential-minus-one', 'log', 'log-plus-one',
    'tanh', 'logistic', 'negate', 'abs', 'sign', 'rsqrt', 'sqrt',
    'compare', 'select', 'and', 'or', 'not', 'xor', 'clamp', 'convert',
    'copy'))


def buffer_bytes(type_spec):
    """Total bytes of one HLO type spec (sums tuple components)."""
    total = 0
    for dtype, shape in _BUF_RE.findall(type_spec):
        n = math.prod(int(d) for d in shape.split(',') if d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def _first_shape(type_spec):
    """Dims tuple of the first (or only) buffer in a type spec."""
    m = _BUF_RE.search(type_spec)
    if not m:
        return None
    return tuple(int(d) for d in m.group(2).split(',') if d)


def _balanced(text, open_idx, open_ch='(', close_ch=')'):
    """Contents of the balanced group starting at text[open_idx]."""
    depth = 0
    for i in range(open_idx, len(text)):
        c = text[i]
        if c == open_ch:
            depth += 1
        elif c == close_ch:
            depth -= 1
            if depth == 0:
                return text[open_idx + 1:i], i
    return text[open_idx + 1:], len(text)


class HloInstr:
    """One instruction of the compiled module."""

    __slots__ = ('name', 'opcode', 'type_spec', 'bytes', 'operands',
                 'sharding', 'group_size', 'called', 'fusion_kind',
                 'call_target', 'file', 'line', 'is_root')

    def __init__(self, name, opcode, type_spec, operands=(), sharding=None,
                 group_size=None, called=(), fusion_kind=None, file=None,
                 line=None, is_root=False, call_target=None):
        self.name = name
        self.opcode = opcode
        self.type_spec = type_spec
        self.bytes = buffer_bytes(type_spec)
        self.operands = tuple(operands)
        self.sharding = sharding
        self.group_size = group_size    # replica group size (collectives)
        self.called = tuple(called)     # names of called computations
        self.fusion_kind = fusion_kind  # kLoop/kOutput/... for fusions
        self.call_target = call_target  # custom-call target name
        self.file = file
        self.line = line
        self.is_root = is_root

    @property
    def shape(self):
        return _first_shape(self.type_spec)

    def __repr__(self):
        return (f'HloInstr({self.name} = {self.type_spec} '
                f'{self.opcode}(...))')


class HloComputation:
    __slots__ = ('name', 'is_entry', 'instrs', 'index')

    def __init__(self, name, is_entry=False):
        self.name = name
        self.is_entry = is_entry
        self.instrs = []
        self.index = {}     # instr name -> HloInstr

    @property
    def is_fusion(self):
        return 'fused' in self.name

    def add(self, instr):
        self.instrs.append(instr)
        self.index[instr.name] = instr


class HloModule:
    """Light op graph of one compiled (per-device) HLO module."""

    __slots__ = ('computations', 'entry', 'num_partitions',
                 'is_scheduled')

    def __init__(self):
        self.computations = {}
        self.entry = None
        self.num_partitions = 1
        self.is_scheduled = False

    def work_computations(self):
        """Entry + called non-fusion computations (while/cond bodies,
        reduce regions): the instructions that are scheduled work.
        Fusion bodies stay register-resident — their HBM traffic is
        the single ``fusion`` call site."""
        for comp in self.computations.values():
            if comp.is_entry or not comp.is_fusion:
                yield comp

    def walk(self):
        """(computation, instr) over every work computation."""
        for comp in self.work_computations():
            for ins in comp.instrs:
                yield comp, ins


def _parse_sharding(line):
    i = line.find('sharding={')
    if i < 0:
        return None
    body, _ = _balanced(line, i + len('sharding='), '{', '}')
    return '{' + body + '}'


def _parse_instr(line, num_partitions, frames):
    m = _INSTR_RE.match(line)
    if not m:
        return None
    root, name, type_spec, opcode = m.groups()
    operand_body, end = _balanced(line, m.end() - 1)
    operands = _OPERAND_NAME_RE.findall(operand_body)
    rest = line[end + 1:]
    group_size = None
    if opcode.split('-start')[0] in costmodel.COLLECTIVE_OPS or \
            opcode.startswith(('all-', 'reduce-scatter', 'collective-')):
        gm = _GROUPS_IOTA_RE.search(rest)
        if gm:
            group_size = int(gm.group(2))
        else:
            gm = _GROUPS_LIST_RE.search(rest)
            if gm:
                group_size = len([d for d in gm.group(1).split(',') if d])
            else:
                group_size = num_partitions
    called = []
    for cm in _CALLED_RE.finditer(rest):
        called.extend(_OPERAND_NAME_RE.findall(cm.group(1)))
    fusion_kind = None
    if opcode == 'fusion':
        km = re.search(r'kind=(\w+)', rest)
        fusion_kind = km.group(1) if km else None
    call_target = None
    if opcode == 'custom-call':
        tm = re.search(r'custom_call_target="([^"]*)"', rest)
        call_target = tm.group(1) if tm else None
    fm = _FRAME_ID_RE.search(rest)
    file, line_no = frames.get(int(fm.group(1)), (None, None)) \
        if fm else (None, None)
    return HloInstr(name, opcode, type_spec, operands=operands,
                    sharding=_parse_sharding(rest), group_size=group_size,
                    called=called, fusion_kind=fusion_kind, file=file,
                    line=line_no, is_root=bool(root),
                    call_target=call_target)


def _resolve_frames(tables):
    """{stack frame id: (file, line)} from the header tables."""
    files = {i: row.strip('"') for i, row in tables['FileNames'].items()}
    locs = {i: dict(_TABLE_FIELD_RE.findall(row))
            for i, row in tables['FileLocations'].items()}
    frames = {}
    for i, row in tables['StackFrames'].items():
        loc = locs.get(int(dict(_TABLE_FIELD_RE.findall(row))
                           .get('file_location_id', 0)), {})
        frames[i] = (files.get(int(loc.get('file_name_id', 0))),
                     int(loc['line']) if 'line' in loc else None)
    return frames


def parse_module(text):
    """Compiled HLO text -> HloModule (computations, instrs, graph)."""
    mod = HloModule()
    current = None
    tables = {'FileNames': {}, 'FileLocations': {}, 'StackFrames': {}}
    table = frames = None
    for line in text.splitlines():
        if line.startswith('HloModule'):
            pm = _NUM_PARTITIONS_RE.search(line)
            if pm:
                mod.num_partitions = int(pm.group(1))
            mod.is_scheduled = 'is_scheduled=true' in line
            continue
        if current is None and line.strip() in tables:
            table = tables[line.strip()]
            continue
        if current is None and table is not None:
            rm = _TABLE_ROW_RE.match(line)
            if rm:
                table[int(rm.group(1))] = rm.group(2)
                continue
            table = None
        cm = _COMP_RE.match(line)
        if cm:
            current = HloComputation(cm.group(2),
                                     is_entry=bool(cm.group(1)))
            mod.computations[current.name] = current
            if current.is_entry:
                mod.entry = current
            continue
        if line.startswith('}'):
            current = None
            continue
        if current is None:
            continue
        if frames is None:
            frames = _resolve_frames(tables)
        ins = _parse_instr(line, mod.num_partitions, frames)
        if ins is not None:
            current.add(ins)
    return mod


# -- collective census + cost -------------------------------------------------

def _collective_base(opcode):
    for suffix in ('-start', '-done'):
        if opcode.endswith(suffix):
            opcode = opcode[:-len(suffix)]
    return opcode if opcode in costmodel.COLLECTIVE_OPS else None


def _collective_bytes(comp, ins, base):
    """Per-device buffer size the ring moves: the operand buffers
    summed (collectives are variadic — a grad-bucketed all-reduce or
    tuple all-to-all moves every piece; the '-start' tuple OUTPUT type
    would double-count, so operand defs are the source of truth)."""
    total = 0
    for op in ins.operands:
        src = comp.index.get(op)
        if src is not None:
            total += src.bytes
    return total or ins.bytes


def _collective_wire_dtype(comp, ins):
    """The dtype actually on the wire for one collective: the element
    type of its byte-dominant operand (quantized collectives move s8
    payloads next to tiny f32 scale buffers — the payload dtype is
    the honest tag).  Falls back to the output type spec."""
    best, best_b = None, -1
    for op in ins.operands:
        src = comp.index.get(op)
        if src is None:
            continue
        m = _BUF_RE.search(src.type_spec)
        if m and src.bytes > best_b:
            best, best_b = m.group(1), src.bytes
    if best is None:
        m = _BUF_RE.search(ins.type_spec)
        best = m.group(1) if m else None
    return best


def _short(type_spec, limit=48):
    return type_spec if len(type_spec) <= limit \
        else type_spec[:limit - 3] + '...'


def collective_census(module, *, bw_gbps=None, latency_us=None,
                      mesh_shape=None, calibration=None):
    """Per-collective census with predicted cost.

    Returns {base_opcode: {calls, bytes, wire_bytes, est_us, phases,
    max_wire_bytes, group_size, axes, file, line}} — ``bytes`` is
    per-device buffer bytes summed over call sites (comparable to the
    telemetry census), ``wire_bytes``/``est_us``/``phases`` the
    cost-model prediction.  With ``mesh_shape`` in hand each replica
    group is decomposed onto its torus axes
    (``costmodel.axes_for_group``) — a dp×tp mesh is no longer costed
    as one flat ring over all chips — and a ``calibration`` table
    substitutes measured alpha/beta.  '-done' halves of async pairs
    are not double counted.
    """
    # ONE walk/cost implementation: the per-instruction index is the
    # source of truth (the trace join reads it directly), and the
    # census is its aggregation by base opcode
    rows = {}
    for r in collective_instrs(module, bw_gbps=bw_gbps,
                               latency_us=latency_us,
                               mesh_shape=mesh_shape,
                               calibration=calibration).values():
        row = rows.setdefault(r['op'], {
            'calls': 0, 'bytes': 0, 'wire_bytes': 0, 'est_us': 0.0,
            'phases': 0, 'max_wire_bytes': 0, 'max_est_us': 0.0,
            'group_size': r['group_size'], 'axes': r['axes'],
            'wire_dtype': r.get('wire_dtype'),
            'file': None, 'line': None})
        row['calls'] += 1
        row['bytes'] += r['bytes']
        row['wire_bytes'] += r['wire_bytes']
        row['est_us'] = round(row['est_us'] + r['est_us'], 3)
        row['phases'] += r['phases']
        if r['wire_bytes'] > row['max_wire_bytes']:
            # group_size/est ride along: on a multi-axis mesh one base
            # opcode mixes group sizes (tp=2 activation vs dp=4 grad
            # all-reduces) and the flag must describe the worst call
            row['max_wire_bytes'] = r['wire_bytes']
            row['max_est_us'] = r['est_us']
            row['group_size'] = r['group_size']
            row['axes'] = r['axes']
            row['wire_dtype'] = r.get('wire_dtype')
            row['file'], row['line'] = r['file'], r['line']
    return rows


def collective_instrs(module, *, bw_gbps=None, latency_us=None,
                      mesh_shape=None, calibration=None):
    """Per-INSTRUCTION collective index of a compiled module — the
    join key for profiled-trace matching (``profiler.trace.
    match_collectives``): a captured trace times ops by instruction
    name, and this index carries each collective instruction's base
    opcode + byte/replica-group signature plus the cost-model
    prediction for exactly that call.

    Returns {instr_name: {op, bytes, wire_bytes, phases, est_us,
    group_size, axes, file, line}} — ``bytes`` is the counted buffer
    (gathered size for all-gather, operand size otherwise), the same
    convention as :func:`collective_census`, whose rows are these
    aggregated by base opcode.  '-done' halves of async pairs are
    skipped (the '-start' op owns the transfer).

    HLO names are unique per COMPUTATION, not per module: when a
    while/scan body reuses an entry-computation name, the later
    instruction keys as ``name@computation`` so no row is lost — the
    trace join strips the ``@…`` qualifier before lookup (a trace
    merges same-named events anyway).
    """
    bw, lat = costmodel.effective_links(bw_gbps, latency_us,
                                        calibration)
    out = {}
    for comp, ins in module.walk():
        if ins.opcode.endswith('-done'):
            continue
        base = _collective_base(ins.opcode)
        if base is None:
            continue
        n = ins.group_size or module.num_partitions
        axes = costmodel.axes_for_group(mesh_shape, n)
        local = _collective_bytes(comp, ins, base)
        counted = local * n if base == 'all-gather' else local
        cost = costmodel.torus_cost(base, counted, axes, bw_gbps=bw,
                                    latency_us=lat,
                                    calibration=calibration)
        key = ins.name if ins.name not in out \
            else f'{ins.name}@{comp.name}'
        out[key] = {
            'op': base, 'bytes': counted,
            'wire_bytes': cost['wire_bytes'],
            'phases': cost['phases'], 'est_us': cost['est_us'],
            'group_size': n, 'axes': cost['axes'],
            'wire_dtype': _collective_wire_dtype(comp, ins),
            'file': ins.file, 'line': ins.line}
    return out


# -- peak-memory liveness -----------------------------------------------------

def _comp_peak(module, comp, memo):
    """(peak_bytes, param_bytes) of one computation, walking the
    schedule: a buffer is born at its defining instruction and dies
    after its last use; called non-fusion computations contribute
    their transient peak at the call site; fusion internals are
    register-resident."""
    if comp.name in memo:
        return memo[comp.name]
    memo[comp.name] = (0, 0)    # cycle guard (self-recursive comps)
    params = sum(i.bytes for i in comp.instrs
                 if i.opcode == 'parameter')
    last_use = {}
    for idx, ins in enumerate(comp.instrs):
        for op in ins.operands:
            last_use[op] = idx
    live = params
    peak = live
    for idx, ins in enumerate(comp.instrs):
        if ins.opcode != 'parameter':
            b = 0 if ins.opcode in _ALIAS_OPS else ins.bytes
            inner = 0
            if ins.opcode != 'fusion':
                for cname in ins.called:
                    sub = module.computations.get(cname)
                    if sub is None or sub.is_fusion:
                        continue
                    sp, spar = _comp_peak(module, sub, memo)
                    # the callee's params alias our operands (already
                    # live here) — only its transient excess stacks
                    inner = max(inner, sp - spar)
            live += b
            peak = max(peak, live + inner)
        for op in set(ins.operands):
            if last_use.get(op) == idx:
                src = comp.index.get(op)
                if src is not None and src.opcode != 'parameter' \
                        and src.opcode not in _ALIAS_OPS:
                    live -= src.bytes
    memo[comp.name] = (peak, params)
    return memo[comp.name]


def peak_memory(module):
    """Per-device high-water HBM estimate (bytes) of the scheduled
    entry computation.  Conservative: donation aliasing is not
    credited, so donated-in-place steps really peak a little lower."""
    if module.entry is None:
        return 0
    peak, _ = _comp_peak(module, module.entry, {})
    return peak


def peak_memory_report(module, top=8):
    """The liveness walk of :func:`peak_memory` over the ENTRY
    computation, instrumented: re-runs the same born-at-def /
    dies-after-last-use schedule tracking the live buffer set, and
    snapshots the largest contributors at the peak instant — so the
    memory observatory can say not just HOW HIGH the predicted
    high-water is but WHICH buffers stack it (with source attribution
    when the HLO carries metadata).

    Returns ``{'peak_bytes', 'param_bytes', 'at_instr',
    'contributors': [{name, opcode, bytes, file, line}, ...]}`` —
    contributors sorted largest-first, capped at `top`, parameters
    folded into one synthetic row.  peak_bytes matches
    :func:`peak_memory` minus callee-transient stacking (entry-local
    buffers only), so it is a floor of the full estimate, never above
    it."""
    empty = {'peak_bytes': 0, 'param_bytes': 0, 'at_instr': None,
             'contributors': []}
    if module.entry is None:
        return empty
    comp = module.entry
    params = sum(i.bytes for i in comp.instrs if i.opcode == 'parameter')
    last_use = {}
    for idx, ins in enumerate(comp.instrs):
        for op in ins.operands:
            last_use[op] = idx
    live_set = {}               # instr name -> bytes (non-param buffers)
    live = params
    peak = live
    at_instr = None
    peak_set = {}
    for idx, ins in enumerate(comp.instrs):
        if ins.opcode != 'parameter':
            if ins.opcode not in _ALIAS_OPS and ins.bytes:
                live_set[ins.name] = ins.bytes
                live += ins.bytes
            if live > peak:
                peak = live
                at_instr = ins.name
                peak_set = dict(live_set)
        for op in set(ins.operands):
            if last_use.get(op) == idx:
                src = comp.index.get(op)
                if src is not None and src.opcode != 'parameter' \
                        and src.opcode not in _ALIAS_OPS:
                    live -= src.bytes
                    live_set.pop(op, None)
    contributors = []
    if params:
        contributors.append({'name': '(parameters)',
                             'opcode': 'parameter', 'bytes': params,
                             'file': None, 'line': None})
    for name, b in sorted(peak_set.items(), key=lambda kv: -kv[1]):
        ins = comp.index.get(name)
        contributors.append({
            'name': name,
            'opcode': ins.opcode if ins is not None else '?',
            'bytes': b,
            'file': ins.file if ins is not None else None,
            'line': ins.line if ins is not None else None})
    contributors.sort(key=lambda c: -c['bytes'])
    return {'peak_bytes': peak, 'param_bytes': params,
            'at_instr': at_instr, 'contributors': contributors[:top]}


# -- rule registry ------------------------------------------------------------

HLO_RULES = {}


def register_hlo_rule(rule_id, severity):
    """Register ``fn(ctx) -> iterable[Finding]`` under `rule_id` (the
    id suppression comments / disable= lists name).  `severity` is the
    WORST level the rule can emit (documentation for tooling that
    lists the registry; each Finding carries its own severity).
    Mirrors rules.register_rule but runs over the compiled-HLO op
    graph."""
    def deco(fn):
        HLO_RULES[rule_id] = (severity, fn)
        fn.rule_id = rule_id
        return fn
    return deco


class HloRuleContext:
    """Everything an HLO rule may inspect for one audit."""

    def __init__(self, module, *, mesh_shape=None, thresholds=None,
                 global_shapes=None, name=None):
        self.module = module
        self.mesh_shape = dict(mesh_shape or {})
        self.thresholds = dict(DEFAULT_HLO_THRESHOLDS)
        self.thresholds.update(thresholds or {})
        # shape tuples of big TRACED intermediates (global, pre-
        # partitioner) — the replicated-giant join key; None when the
        # caller could not re-trace the step
        self.global_shapes = global_shapes
        self.name = name
        self.summary = {'n_partitions': module.num_partitions,
                        'mesh': self.mesh_shape or None}
        self._census = None

    def census(self):
        if self._census is None:
            self._census = collective_census(
                self.module,
                bw_gbps=self.thresholds['link_bw_gbps'],
                latency_us=self.thresholds['link_latency_us'],
                mesh_shape=self.mesh_shape or None,
                calibration=self.thresholds.get('calibration'))
            self.summary['collectives'] = self._census
            self.summary['collective_wire_bytes'] = sum(
                r['wire_bytes'] for r in self._census.values())
            self.summary['collective_est_us'] = round(sum(
                r['est_us'] for r in self._census.values()), 3)
        return self._census


def run_hlo_rules(ctx, disable=()):
    out = []
    for rule_id, (_, fn) in HLO_RULES.items():
        if rule_id in disable:
            continue
        out.extend(fn(ctx))
    return out


def _mib(b):
    return b / (1 << 20)


def _maybe_local_shard(shape, global_shapes, mesh_shape, n_partitions):
    """True when `shape` could equally be the per-device SHARD of a
    larger traced global: scaling its dimensions by mesh-axis factors
    (one axis per dim, or several axes across several dims — GSPMD
    shards 2D too) lands on another global shape.  Such a buffer is
    ambiguous — the bare dims tuple cannot distinguish 'replicated at
    full traced shape' from 'correctly partitioned slice of a bigger
    intermediate that happens to collide'."""
    factors = {1}
    for s in (v for v in mesh_shape.values() if v > 1):
        factors |= {f * s for f in factors}
    factors.add(max(n_partitions, 1))
    factors.discard(1)
    if not factors or len(shape) > 8:
        return False
    per_dim = (1,) + tuple(sorted(factors))
    total = max(factors)    # can't shard more ways than devices exist

    def expand(cur, d, scale):
        if d == len(cur):
            return scale > 1 and cur in global_shapes
        for k in per_dim:
            if scale * k > total:
                continue
            nxt = cur if k == 1 else \
                cur[:d] + (cur[d] * k,) + cur[d + 1:]
            if expand(nxt, d + 1, scale * k):
                return True
        return False

    return expand(shape, 0, 1)


@register_hlo_rule('replicated-giant-hlo', HIGH)
def replicated_giant_hlo(ctx):
    """Per-device buffers still at a FULL traced (global) shape.

    The jaxpr rule can only prove replication for constant-derived
    values; after the partitioner every buffer in the per-device
    module IS a per-device buffer, so an intermediate whose local
    shape still equals the global shape of a traced intermediate was
    left replicated — input-derived or not."""
    if ctx.module.num_partitions <= 1:
        return
    threshold = ctx.thresholds['replicated_bytes']
    for comp, ins in ctx.module.walk():
        if (ins.opcode in _ALIAS_OPS or ins.is_root
                or ins.bytes < threshold):
            continue
        shape = ins.shape
        if shape is None:
            continue
        if ctx.global_shapes is not None and shape not in ctx.global_shapes:
            continue    # partitioned: its global shape was bigger
        verified = ctx.global_shapes is not None and not _maybe_local_shard(
            shape, ctx.global_shapes, ctx.mesh_shape,
            ctx.module.num_partitions)
        yield Finding(
            'replicated-giant-hlo', HIGH if verified else WARN,
            f'{ins.opcode} buffer {_short(ins.type_spec)} '
            f'({_mib(ins.bytes):.0f} MiB) '
            + ('still has its full traced shape after the SPMD '
               'partitioner: it is materialized replicated in EVERY '
               f'device\'s HBM ({ctx.module.num_partitions} devices). '
               'Derive it from sharded operands or wrap it in '
               'jax.lax.with_sharding_constraint.'
               if verified else
               'is large per device after partitioning; check its '
               'sharding (replication unverified: '
               + ('it also matches a shard of a larger traced '
                  'intermediate).'
                  if ctx.global_shapes is not None else
                  'trace unavailable).')),
            file=ins.file, line=ins.line, origin='hlo')


@register_hlo_rule('collective-cost', HIGH)
def collective_cost(ctx):
    """Oversized or avoidably-placed collectives (EQuARX-style)."""
    census = ctx.census()
    warn_at = ctx.thresholds['collective_wire_warn']
    high_at = ctx.thresholds['collective_wire_high']
    for base, row in census.items():
        worst = row['max_wire_bytes']
        if worst < warn_at:
            continue
        yield Finding(
            'collective-cost', HIGH if worst >= high_at else WARN,
            f'{base} over {row["group_size"]} devices puts '
            f'{_mib(worst):.0f} MiB on the ICI wire in one call '
            f'(~{row["max_est_us"]:.0f} us ring estimate): consider '
            'sharding the value, reduce-scatter + sharded consumer '
            'instead of all-reduce, or overlapping via async '
            'collectives.',
            file=row['file'], line=row['line'], origin='hlo')
    # all-gather whose every consumer is elementwise: the gather could
    # move AFTER the elementwise work (or vanish) by keeping it sharded
    seen_lines = set()
    for comp, ins in ctx.module.walk():
        if _collective_base(ins.opcode) != 'all-gather' \
                or ins.opcode.endswith('-done'):
            continue
        if (ins.file, ins.line) in seen_lines:
            continue
        out_names = {ins.name}
        # async pair: consumers read the -done instr's output
        for other in comp.instrs:
            if other.opcode.endswith('-done') and \
                    ins.name in other.operands:
                out_names.add(other.name)
        consumers = [o for o in comp.instrs
                     if o is not ins and not o.opcode.endswith('-done')
                     and out_names.intersection(o.operands)]
        if not consumers:
            continue
        if all(c.opcode in _ELEMENTWISE_OPS
               or (c.opcode == 'fusion' and c.fusion_kind == 'kLoop')
               for c in consumers):
            seen_lines.add((ins.file, ins.line))
            yield Finding(
                'collective-cost', WARN,
                f'all-gather of {_short(ins.type_spec)} feeds only '
                'elementwise '
                'consumers: the elementwise work could run on the '
                'sharded value and the gather move after it (or into '
                'the consumer that actually needs it).',
                file=ins.file, line=ins.line, origin='hlo')


@register_hlo_rule('resharding', WARN)
def resharding(ctx):
    """all-to-all = the partitioner resharding between adjacent ops
    whose requested shardings conflict (e.g. P('dp', None) feeding an
    op constrained to P(None, 'dp')).

    Always WARN, never HIGH: a user-requested collective
    (distributed.alltoall in an expert-parallel layer) lowers to the
    SAME opcode and the HLO text cannot tell the two apart — a
    deliberate MoE dispatch must not fail the zero-high gates."""
    for comp, ins in ctx.module.walk():
        if _collective_base(ins.opcode) != 'all-to-all' \
                or ins.opcode.endswith('-done'):
            continue
        local = _collective_bytes(comp, ins, 'all-to-all')
        yield Finding(
            'resharding', WARN,
            f'all-to-all ({_short(ins.type_spec)}, '
            f'{_mib(local):.1f} MiB per device): if not a deliberate '
            'collective (expert dispatch), the partitioner inserted '
            'it because adjacent ops request conflicting shardings — '
            'align the shardings (or constrain once, early) to delete '
            'the transpose traffic.',
            file=ins.file, line=ins.line, origin='hlo')


@register_hlo_rule('peak-memory', HIGH)
def peak_memory_rule(ctx):
    """Liveness high-water vs the HBM budget."""
    peak = peak_memory(ctx.module)
    ctx.summary['peak_bytes'] = peak
    # liveness fidelity: the walk follows instruction order, which is
    # the real schedule only when the backend emitted one
    ctx.summary['peak_schedule'] = (
        'scheduled' if ctx.module.is_scheduled else 'def-order')
    budget = ctx.thresholds['hbm_bytes']
    ctx.summary['hbm_budget_bytes'] = budget
    frac = ctx.thresholds['hbm_warn_frac']
    if peak >= budget:
        sev = HIGH
    elif peak >= frac * budget:
        sev = WARN
    else:
        return
    yield Finding(
        'peak-memory', sev,
        f'estimated per-device peak {peak / (1 << 30):.2f} GiB vs '
        f'{budget / (1 << 30):.2f} GiB HBM budget'
        + (f' ({peak / budget:.0%})' if budget else '')
        + ': the step will '
        + ('OOM' if sev == HIGH else 'run out of headroom')
        + ' on the real chip. Shard the largest live buffers, enable '
          'remat (strategy.recompute), or lower the batch.',
        origin='hlo')


# -- entry points -------------------------------------------------------------

def auto_shardings(mesh, example_args):
    """Forced-mesh heuristic for a bare callable: shard dim 0 of every
    array leaf over the mesh's first >1 axis when divisible, replicate
    the rest.  The compile-choke-point integrations pass their REAL
    shardings instead; this is for ``tpu_lint --hlo --jaxpr`` style
    audits where only shapes are known."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    axis = next((a for a in mesh.axis_names if mesh.shape[a] > 1), None)
    if axis is None:
        return None

    def leaf_sharding(leaf):
        shape = getattr(leaf, 'shape', None)
        if shape and len(shape) >= 1 and shape[0] % mesh.shape[axis] == 0:
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())

    return tuple(jax.tree_util.tree_map(leaf_sharding, a)
                 for a in example_args)


def global_big_shapes_of(closed, threshold):
    """Shape tuples of intermediates >= threshold bytes in an already-
    traced closed jaxpr — the pre-partitioner (global) side of the
    replicated-giant join.  Top-level outputs are excluded (returning
    params is legitimate).  analysis.lint stashes this on its report
    so the HLO escalation at the choke points can skip re-tracing."""
    from . import walker as _w
    shapes = set()
    outset = set(closed.jaxpr.outvars)
    for _, eqn in _w.walk(closed.jaxpr):
        for ov in eqn.outvars:
            if ov in outset:
                continue
            if _w.aval_bytes(ov.aval) >= threshold:
                shapes.add(tuple(int(d) for d in ov.aval.shape))
    return shapes


def _global_big_shapes(fn, example_args, example_kwargs, threshold):
    """Trace `fn` and collect its big global shapes; None when the
    trace fails (the audit then degrades to WARN)."""
    try:
        from . import walker
        closed = walker.trace_jaxpr(fn, *example_args, **example_kwargs)
    except Exception:
        return None
    return global_big_shapes_of(closed, threshold)


def audit_text(text, *, mesh=None, thresholds=None, disable=(),
               global_shapes=None, name=None):
    """Run the HLO rules over compiled HLO text already in hand
    (ParallelTrainer's census path).  Returns a LintReport whose
    ``extras`` carry the census / peak-memory summary."""
    from .ast_lint import apply_suppressions
    module = parse_module(text)
    mesh_shape = dict(getattr(mesh, 'shape', mesh or {}) or {})
    ctx = HloRuleContext(module, mesh_shape=mesh_shape,
                         thresholds=thresholds,
                         global_shapes=global_shapes, name=name)
    findings = run_hlo_rules(ctx, disable=disable)
    ctx.census()                      # always fill the summary
    ctx.summary.setdefault('peak_bytes', peak_memory(module))
    findings = [f for f in apply_suppressions(findings)
                if f.rule not in disable]
    report = LintReport(findings, name=name)
    report.extras = ctx.summary
    return report


def lower_text(fn, *example_args, jit_kwargs=None, lower_cache=None,
               cache_key=None, **example_kwargs):
    """``jax.jit(fn, **jit_kwargs).lower(...).compile().as_text()``
    with an optional cross-caller memo: when `lower_cache` (a plain
    dict) holds `cache_key`, the trace+lower+compile is skipped
    entirely.  This is how ``tpu_lint --plan`` and ``--hlo`` share
    ONE lowering per (target, mesh) pair instead of paying the
    partitioner twice for the same program.

    Keyed lowerings are additionally backed by the PERSISTENT compile
    cache's text tier (core.compile_cache): a repeated ``tpu_lint``
    invocation on unchanged targets reads its candidate modules off
    disk instead of compiling them again — dozens of planner
    candidates come back in seconds.  `cache_key` must be a
    deterministic, process-independent value (analysis.targets builds
    them from resolved specs and shapes); the persistent fingerprint
    folds in the jax version, backend, device count and package
    sources, so code or environment drift invalidates cleanly."""
    import jax
    if lower_cache is not None and cache_key is not None \
            and cache_key in lower_cache:
        return lower_cache[cache_key]
    fp = None
    if cache_key is not None:
        from ..core import compile_cache as _cc
        if _cc.enabled():
            fp = _cc.fingerprint('lower-text', key=cache_key)
            if fp is not None:
                text = _cc.get_text(fp, name='lower_text')
                if text is not None:
                    if lower_cache is not None:
                        lower_cache[cache_key] = text
                    return text
    text = jax.jit(fn, **(jit_kwargs or {})).lower(
        *example_args, **example_kwargs).compile().as_text()
    if fp is not None:
        from ..core import compile_cache as _cc
        _cc.put_text(fp, text, name='lower_text')
    if lower_cache is not None and cache_key is not None:
        lower_cache[cache_key] = text
    return text


def audit(fn, *example_args, mesh=None, in_shardings='auto',
          out_shardings=None, donate_argnums=(), jit_kwargs=None,
          thresholds=None, disable=(), name=None, global_shapes=None,
          lower_cache=None, cache_key=None, **example_kwargs):
    """Lower `fn` through the SPMD partitioner and audit the compiled
    per-device HLO.  No device execution: ``jit.lower().compile()``
    only — runs fine under JAX_PLATFORMS=cpu with
    --xla_force_host_platform_device_count forced mesh axes.

    example_args: arrays / pytrees / jax.ShapeDtypeStruct placeholders.
    mesh: the jax.sharding.Mesh to partition over.
    in_shardings: 'auto' (dim-0-over-first-axis heuristic via
    auto_shardings), an explicit jit in_shardings tree, or None (let
    jit infer — single-device unless args carry shardings).
    jit_kwargs: full jax.jit kwargs from a compile choke point
    (ParallelTrainer passes its real in/out shardings + donation) —
    overrides in/out_shardings/donate_argnums.
    lower_cache / cache_key: see ``lower_text`` — reuse (or publish)
    the compiled HLO text of this exact (fn, shardings) pair.
    """
    name = name or getattr(fn, '__name__', None) or 'step'
    thr = dict(DEFAULT_HLO_THRESHOLDS)
    thr.update(thresholds or {})
    if jit_kwargs is None:
        jit_kwargs = {}
        if in_shardings == 'auto':
            if mesh is not None:
                sh = auto_shardings(mesh, example_args)
                if sh is not None:
                    jit_kwargs['in_shardings'] = sh
        elif in_shardings is not None:
            jit_kwargs['in_shardings'] = in_shardings
        if out_shardings is not None:
            jit_kwargs['out_shardings'] = out_shardings
        if donate_argnums:
            jit_kwargs['donate_argnums'] = tuple(donate_argnums)
    text = lower_text(fn, *example_args, jit_kwargs=jit_kwargs,
                      lower_cache=lower_cache, cache_key=cache_key,
                      **example_kwargs)
    if global_shapes is None:
        # a caller that already traced the step (the jaxpr lint runs
        # first at every choke point) can pass its shapes and skip
        # this second abstract trace
        global_shapes = _global_big_shapes(
            fn, example_args, example_kwargs, thr['replicated_bytes'])
    return audit_text(text, mesh=mesh, thresholds=thr,
                      disable=disable, global_shapes=global_shapes,
                      name=name)
