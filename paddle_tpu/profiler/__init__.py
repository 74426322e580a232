"""Profiler (reference: python/paddle/fluid/profiler.py + platform/
profiler).  TPU-native: wraps jax.profiler traces (viewable in
TensorBoard/XProf) and adds host-side step timers — the reference's
nvprof hooks have no TPU meaning.  `op_summary` is the per-op table
(reference stop_profiler(sorted_key=...) prints per-op CUDA times;
here rows come from the step's optimized HLO, ranked by memory
traffic — the honest time proxy on an HBM-bound chip).

The trace a start/stop window emits is not just for the TensorBoard
UI any more: ``profiler.trace`` parses the perfetto ``*.trace.json.gz``
into per-op durations (stdlib gzip+json), and ``stop_profiler``
returns a parsed :class:`trace.TraceProfile` when asked — profiled
collectives join the ``analysis.hlo`` census by instruction name and
become ``collective_observed`` telemetry events (the calibration-fit
input).  The sampled in-training capture loop lives in
``telemetry.profile`` (``fit(profile=…)``,
``ParallelTrainer(profile=…)``, ``PADDLE_TPU_PROFILE``).
"""
import contextlib
import sys

import jax

# THE step timer and THE span of the stack live in telemetry; the
# reference's RecordEvent is that span (a jax.profiler.TraceAnnotation
# for its extent) under its reference name
from ..telemetry import StepTimer, span as RecordEvent  # noqa: F401
from . import trace  # noqa: F401
from .trace import (  # noqa: F401
    TraceProfile, parse_trace, find_traces, match_collectives)

__all__ = ['Profiler', 'start_profiler', 'stop_profiler', 'profiler',
           'reset_profiler', 'cuda_profiler', 'StepTimer', 'RecordEvent',
           'op_summary', 'trace', 'TraceProfile', 'parse_trace',
           'find_traces', 'match_collectives']


def op_summary(fn, *args, sorted_by='total', top=25, stream=None,
               print_table=True, hlo_text=None, totals=None):
    """Per-op summary table for one jitted step (reference
    fluid/profiler.py prints a per-op table via
    stop_profiler(sorted_key); there the rows are CUDA kernel times —
    here they come from the step's compiled, optimized HLO module).

    `fn` is a jitted callable (or anything `jax.jit` accepts) and
    `args` its example inputs; the step is lowered+compiled but NOT
    executed.  Each row aggregates one HLO opcode post-fusion:
    calls, output bytes (the HBM write traffic — the time proxy on a
    bandwidth-bound chip), and its ratio of the module total.  Rows
    cover the ENTRY computation plus while/cond bodies (counted once,
    not by trip count); fusion internals are folded into their single
    `fusion` call-site row.
    Module-level flops / bytes-accessed from
    `compiled.cost_analysis()` head the table when XLA reports them.

    sorted_by: 'total'/'bytes' ranks by bytes, 'calls' by call count.
    Returns the rows as a list of dicts (opcode, calls, bytes, ratio).

    hlo_text: compiled HLO text already in hand (a trainer's
    ``compiled_text()``, the planner's lowering memo, or the
    persistent compile cache's text tier) — skips the lower+compile
    entirely, so profiling a just-trained fn is free.  Module-total
    cost_analysis rows need the live compiled object: pass them via
    ``totals`` when the caller has them (ParallelTrainer stashes
    them at its one lowering), else they are omitted on that path.
    """
    if sorted_by not in ('total', 'bytes', 'calls'):
        raise ValueError(
            f"sorted_by must be 'total', 'bytes' or 'calls', "
            f'got {sorted_by!r}')
    totals = dict(totals or {})
    if hlo_text is None:
        jitted = fn if hasattr(fn, 'lower') else jax.jit(fn)
        compiled = jitted.lower(*args).compile()
        hlo_text = compiled.as_text()
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):  # older jax returns [dict]
                ca = ca[0] if ca else {}
            for key in ('flops', 'bytes accessed'):
                if ca.get(key):
                    totals[key] = float(ca[key])
        except Exception:       # backend without cost analysis
            pass

    # the HLO-text grammar lives in ONE place: analysis.hlo's parser
    # (walk() = ENTRY + while/cond bodies, fusion internals folded
    # into their call-site `fusion` row — exactly the rows we want)
    from ..analysis import hlo as _hlo
    agg = {}
    for _comp, ins in _hlo.parse_module(hlo_text).walk():
        if ins.opcode in ('parameter', 'constant', 'tuple',
                          'get-tuple-element'):
            continue        # plumbing, not work
        row = agg.setdefault(ins.opcode, {'opcode': ins.opcode,
                                          'calls': 0, 'bytes': 0})
        row['calls'] += 1
        row['bytes'] += ins.bytes
    grand = sum(r['bytes'] for r in agg.values()) or 1
    key = 'calls' if sorted_by == 'calls' else 'bytes'
    rows = sorted(agg.values(), key=lambda r: r[key], reverse=True)
    for r in rows:
        r['ratio'] = r['bytes'] / grand
    if print_table:
        out = stream or sys.stdout
        print('------------------------- op summary '
              '-------------------------', file=out)
        for k, v in totals.items():
            print(f'module {k}: {v:.3e}', file=out)
        print(f'{"op":<28}{"calls":>8}{"out bytes":>14}{"ratio":>8}',
              file=out)
        for r in rows[:top]:
            print(f'{r["opcode"]:<28}{r["calls"]:>8}'
                  f'{r["bytes"]:>14,}{r["ratio"]:>8.2%}', file=out)
        if len(rows) > top:
            rest = sum(r['bytes'] for r in rows[top:])
            print(f'{"... (" + str(len(rows) - top) + " more)":<28}'
                  f'{"":>8}{rest:>14,}{rest / grand:>8.2%}', file=out)
    return rows

_active_logdir = None


def reset_profiler():
    """Drop profiling state gathered so far (reference:
    fluid.profiler.reset_profiler).  XLA traces are windowed by
    start/stop, so there is no cumulative op table to clear — an active
    trace is aborted and restarted on the same logdir."""
    global _active_logdir
    if _active_logdir is not None:
        logdir = _active_logdir
        jax.profiler.stop_trace()
        jax.profiler.start_trace(logdir)


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """nvprof hook (reference: fluid.profiler.cuda_profiler) — no CUDA
    on TPU, so this delegates to the XLA trace so legacy scripts still
    produce a usable (XProf) profile."""
    import warnings
    warnings.warn('cuda_profiler has no CUDA meaning on TPU; recording '
                  'an XLA trace instead (view with tensorboard)')
    start_profiler()
    try:
        yield
    finally:
        stop_profiler()


def start_profiler(state=None, tracer_option=None,
                   logdir='/tmp/paddle_tpu_profile'):
    """Begin a device+host trace (reference: fluid.profiler.start_profiler).
    View with tensorboard --logdir <logdir>."""
    global _active_logdir
    jax.profiler.start_trace(logdir)
    _active_logdir = logdir
    return logdir


def stop_profiler(sorted_key=None, profile_path=None, parse=False):
    """End the window.  Returns the logdir (legacy contract), or —
    with ``parse=True`` — the parsed :class:`trace.TraceProfile` of
    the newest emitted trace (None when nothing was written)."""
    global _active_logdir
    jax.profiler.stop_trace()
    out = _active_logdir
    _active_logdir = None
    if parse and out is not None:
        files = find_traces(out)
        return parse_trace(files[-1]) if files else None
    return out


@contextlib.contextmanager
def profiler(state=None, sorted_key=None,
             logdir='/tmp/paddle_tpu_profile'):
    start_profiler(state, logdir=logdir)
    try:
        yield
    finally:
        stop_profiler(sorted_key)


class Profiler:
    """paddle.profiler.Profiler-style context (2.x API shape)."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 logdir='/tmp/paddle_tpu_profile'):
        self.logdir = logdir
        self.timer = StepTimer()
        self._running = False

    def start(self):
        start_profiler(logdir=self.logdir)
        self._running = True
        self.timer.start()

    def stop(self):
        if self._running:
            stop_profiler()
            self._running = False

    def step(self, sync=None):
        self.timer.stop(sync)
        self.timer.start()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def summary(self, *a, **k):
        return self.timer.summary()
