"""paddle_tpu.telemetry — unified, sync-free run telemetry.

The one structured record of *what happened during a run*:

* **spans** — ``with telemetry.span('serve.plan'):`` a profiler
  annotation always, a record (start, end, ids, rid) when enabled;
* **counters / gauges** — retrace counts, dataloader host-wait
  seconds, collective bytes;
* **typed events** — ``compile``, ``retrace``,
  ``checkpoint_save/commit/restore/quarantine``, ``preemption``,
  ``nan_skip/rollback/fatal``, ``lint_finding``, ``steps`` (flushed
  per-step scalars), emitted by hapi / parallel / jit / resilience /
  analysis / io at their natural boundaries;
* a **flight recorder** — the bounded ring of the last N events that
  resilience dumps to ``<ckpt_dir>/flightrec-<step>.json`` on SIGTERM
  preemption, NaN rollback, or crash, so a preempted TPU worker is
  post-mortemable without live logs;
* **exporters** — a rank-tagged JSONL stream per host
  (``telemetry-r<rank>.jsonl``) merged by ``tools/run_report.py``
  into step-time percentiles, compile totals, retrace counts, the
  device-step vs host-wait split, and the resilience event timeline.

The contract that makes this safe to leave on: **the step path is
sync-free**.  Per-step scalars (loss, tokens) are buffered as DEVICE
arrays by ``StepAccumulator`` and read back only every
``flush_interval`` steps (default 32) — by then they are long
computed, so the flush never stalls the XLA queue.  Everything else
emits at boundary rate (compile / checkpoint / epoch), never per step.
``tests/test_event_telemetry.py`` pins this with a device→host
transfer guard and the ``analysis`` host-sync rule.

Usage::

    from paddle_tpu import telemetry
    telemetry.enable('/ckpt/run7/telemetry')     # JSONL + step stats
    ...train...                                  # emission is wired in
    telemetry.dump_flight('/ckpt/run7/flightrec-manual.json')

    $ python tools/run_report.py /ckpt/run7/telemetry

Hard kill switch: ``PADDLE_TPU_TELEMETRY=0`` (every entry point
no-ops).  Without ``enable()`` the recorder still keeps the in-memory
flight ring + counters (boundary-rate, negligible) so crash/preemption
dumps work out of the box; ``enable()`` adds the JSONL stream and the
per-step accumulation.
"""
import os
import sys

from . import recorder as _recorder
from .recorder import (  # noqa: F401
    Recorder, Span, get_recorder, reset, hard_off, enabled,
    EVENT_KINDS)
from .stepstats import (  # noqa: F401
    StepAccumulator, StepTimer, percentiles)
from .exporters import (  # noqa: F401
    JsonlWriter, ScalarAdapter, TensorBoardWriter, TeeWriter)
from .profile import (  # noqa: F401
    ProfileSchedule, StepProfiler, step_profiler, capture,
    resolve_schedule)
from .live import (  # noqa: F401
    LiveAggregator, RollingWindow, RateCounter)
from .monitors import (  # noqa: F401
    SLOMonitor, DriftMonitor, MemoryMonitor)
from .memory import (  # noqa: F401
    MemConfig, MemorySampler, resolve_memstats, note_compiled,
    maybe_note_compiled, ensure_sampler, stop_sampler)
from .httpd import (  # noqa: F401
    MetricsServer, resolve_metrics_port, attach_source)
from .cluster import (  # noqa: F401
    ClusterPublisher, ClusterAggregator, ClusterPlane,
    enable_cluster_plane, resolve_cluster_stats)

__all__ = [
    'Recorder', 'Span', 'get_recorder', 'reset', 'hard_off',
    'EVENT_KINDS', 'StepAccumulator', 'StepTimer', 'percentiles',
    'JsonlWriter', 'ScalarAdapter', 'TensorBoardWriter', 'TeeWriter',
    'ProfileSchedule', 'StepProfiler', 'step_profiler', 'capture',
    'resolve_schedule',
    'LiveAggregator', 'RollingWindow', 'RateCounter',
    'SLOMonitor', 'DriftMonitor', 'MemoryMonitor',
    'MemConfig', 'MemorySampler', 'resolve_memstats', 'note_compiled',
    'maybe_note_compiled', 'ensure_sampler', 'stop_sampler',
    'MetricsServer', 'resolve_metrics_port', 'attach_source',
    'ClusterPublisher', 'ClusterAggregator', 'ClusterPlane',
    'enable_cluster_plane', 'resolve_cluster_stats',
    'enable', 'disable', 'enabled', 'active',
    'event', 'add', 'set_gauge', 'span', 'events', 'streaming',
    'step_accumulator', 'dump_flight', 'flight_dir',
]

span = Span      # the module-level name every call site uses
_prev_excepthook = None
_crash_dir = None


def active():
    """True when telemetry records at all (the default; in-memory
    flight ring + counters).  False only under PADDLE_TPU_TELEMETRY=0."""
    return not hard_off()


def enable(log_dir=None, flush_interval=32, crash_dump=True,
           max_events=None, tensorboard=False):
    """Turn on full telemetry: stream events to
    ``<log_dir>/telemetry-r<rank>.jsonl``, activate the sync-free
    per-step accumulators in hapi/ParallelTrainer at
    ``flush_interval``, and (default) install a crash hook that dumps
    the flight recorder on an unhandled exception.

    log_dir=None keeps everything in memory (step accumulation and
    flight dumps still work; nothing streams to disk).

    tensorboard=True additionally writes TensorBoard-native event
    files (``events.out.tfevents.*``) next to the JSONL: the SAME
    buffered device scalars — ``steps`` flushes and ``scalar``
    records — become TB scalar points at their flush boundary, so the
    export adds zero per-step host syncs (stdlib-only writer, see
    exporters.TensorBoardWriter)."""
    global _crash_dir
    if hard_off():
        return None
    rec = get_recorder()
    if max_events is not None:
        # resize the ring in place, keeping the newest events
        from collections import deque
        rec._events = deque(rec._events, maxlen=max_events)
    rec.flush_interval = max(1, int(flush_interval))
    if log_dir is not None:
        writer = JsonlWriter(log_dir)
        if tensorboard:
            writer = TeeWriter(writer, TensorBoardWriter(log_dir))
        old = rec.attach_writer(writer)
        if old is not None:
            old.close()
        _crash_dir = os.path.abspath(log_dir)
    _recorder._enabled = True
    if crash_dump:
        _install_crash_hook()
    meta = {'pid': os.getpid(), 'argv': list(sys.argv),
            'flush_interval': rec.flush_interval}
    try:
        import jax
        meta['backend'] = jax.default_backend()
        meta['process_count'] = jax.process_count()
    except Exception:
        pass
    rec.event('run_meta', **meta)
    return rec


def disable():
    """Detach the JSONL writer and stop per-step accumulation; the
    in-memory flight ring keeps recording (see active())."""
    _recorder._enabled = False
    rec = get_recorder()
    w = rec.attach_writer(None)
    if w is not None:
        w.close()
    _remove_crash_hook()


def flight_dir():
    """The directory crash dumps land in (the enable() log_dir), or
    None — call sites with a better home (a checkpoint dir) pass their
    own path to dump_flight()."""
    return _crash_dir


# -- module-level conveniences (the emission API call sites use) --------------

def event(kind, **data):
    if hard_off():
        return None
    return get_recorder().event(kind, **data)


def add(name, n=1):
    if hard_off():
        return
    get_recorder().add(name, n)


def set_gauge(name, value):
    if hard_off():
        return
    get_recorder().set_gauge(name, value)


def events(kind=None):
    return get_recorder().events(kind)


def streaming():
    """True when a JSONL writer or a subscriber reads the event stream
    beyond the flight ring: emitters of costly payloads ask first."""
    rec = get_recorder()
    return not hard_off() and (
        rec.writer is not None or bool(rec._subscribers))


def step_accumulator(tag='train', flush_interval=None):
    """A StepAccumulator for a step loop, or None when full telemetry
    is off — loops guard with ``if acc is not None``."""
    if not enabled():
        return None
    return StepAccumulator(tag=tag, flush_interval=flush_interval)


def dump_flight(path):
    """Write the flight-recorder JSON to `path` (atomic; never
    raises).  Returns the path or None."""
    if hard_off():
        return None
    return get_recorder().dump_flight(path)


# -- crash hook ---------------------------------------------------------------

def _crash_hook(exc_type, exc, tb):
    try:
        d = _crash_dir or '.'
        from .recorder import _rank
        get_recorder().event_unlocked(
            'crash', error=repr(exc)[:300],
            exc_type=getattr(exc_type, '__name__', str(exc_type)))
        get_recorder().dump_flight(
            os.path.join(d, f'flightrec-crash-r{_rank()}.json'))
    except Exception:
        pass
    (_prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)


def _install_crash_hook():
    global _prev_excepthook
    if sys.excepthook is _crash_hook:
        return
    _prev_excepthook = sys.excepthook
    sys.excepthook = _crash_hook


def _remove_crash_hook():
    global _prev_excepthook
    if sys.excepthook is _crash_hook:
        sys.excepthook = _prev_excepthook or sys.__excepthook__
        _prev_excepthook = None
