"""Memory observatory — the three-source HBM truth plane.

PR-4's liveness walk (:func:`analysis.hlo.peak_memory`) gates planner
candidates against ``--hbm-gb`` and PR-16's supervisor swaps plans on
its say-so, yet nothing ever checked that estimate against what XLA
actually reserves or what devices actually hold live.  This module
closes the memory half of the predicted-vs-observed loop the same way
PR-8 closed the collective-time half, with THREE sources joined
per-module:

* **predicted** — the PR-4 liveness peak, re-derived from the
  compiled module's own HLO text (so prediction and compilation
  describe the same program, not the pre-SPMD trace);
* **compiled** — XLA's ``compiled.memory_analysis()`` (argument /
  output / temp / alias / generated-code bytes), extracted at every
  compile choke point (ParallelTrainer, hapi ``train_batch``,
  ``to_static``, the serving module set, compile-cache warm starts)
  and emitted as one ``memory_compiled`` event per module;
* **live** — a :class:`MemorySampler` thread (default OFF,
  ``PADDLE_TPU_MEMSTATS``, watchdog posture) reading
  ``device.memory_stats()`` on TPU with a ``jax.live_arrays()``
  aval-bytes census fallback on CPU, publishing
  ``memory.device_bytes`` / ``memory.host_rss`` gauges and
  boundary-rate ``memory_sample`` events.

Cost posture — extraction is **free where a Compiled already exists**
(the trainer's ``compiled_text()`` memo, the compile cache's
``aot_compile`` store path) and **armed-only elsewhere**: hapi / jit /
serving choke points and warm-start deserializes pay an extra
``lower().compile()`` per module (measured ~2x one compile, amortized
by the persistent XLA cache when it is on), so they extract only under
``PADDLE_TPU_MEMSTATS``.  The sampler itself never syncs the step
path: ``memory_stats()`` is a host-side read and the live-arrays
census touches only avals — tests/test_event_memory.py proves the
armed posture under a device→host transfer guard.

Consumers: ``tools/run_report.py`` renders the per-module three-way
table (predicted/compiled ratio, calibratable like
``collectives_cmp``); :mod:`telemetry.httpd` serves :func:`snapshot`
as ``/memory.json``; :mod:`telemetry.cluster` frames carry the gauges
as per-rank columns; :class:`telemetry.monitors.MemoryMonitor` turns
the live high-water into an exactly-once ``memory_pressure`` edge the
plan supervisor re-plans on (with a tightened budget).
"""
import os
import threading
import time

__all__ = ['MemConfig', 'resolve_memstats', 'armed', 'note_compiled',
           'maybe_note_compiled', 'MemorySampler', 'ensure_sampler',
           'stop_sampler', 'snapshot', 'reset_modules', 'host_rss_bytes',
           'device_memory_stats', 'live_arrays_bytes', 'MEMSTATS_ENV']

MEMSTATS_ENV = 'PADDLE_TPU_MEMSTATS'

_MONO = time.monotonic


class MemConfig:
    """Sampler/monitor knobs, env-parsable like the watchdog Budget.

    interval_s   sampler cadence (seconds; boundary rate, never
                 per-step)
    budget_gb    live-bytes budget the MemoryMonitor fires against
                 (None: the monitor stays dormant — sensing without
                 actuation)
    watermark    fire when device_bytes > budget * watermark
    rearm_frac   re-arm when device_bytes <= budget * watermark *
                 rearm_frac (hysteresis)
    """

    def __init__(self, interval_s=10.0, budget_gb=None, watermark=0.9,
                 rearm_frac=0.7):
        self.interval_s = max(0.05, float(interval_s))
        self.budget_gb = None if budget_gb is None else float(budget_gb)
        self.watermark = float(watermark)
        self.rearm_frac = float(rearm_frac)

    @property
    def budget_bytes(self):
        if self.budget_gb is None:
            return None
        return int(self.budget_gb * (1 << 30))

    @classmethod
    def from_env(cls, text):
        """``PADDLE_TPU_MEMSTATS`` grammar: unset/'0'/'off'/'false' ->
        None; '1'/'on'/'true' -> defaults; else ``k=v,...`` with keys
        interval / budget_gb / watermark / rearm."""
        if text is None:
            return None
        text = text.strip()
        if text.lower() in ('', '0', 'off', 'false', 'no'):
            return None
        if text.lower() in ('1', 'on', 'true', 'yes'):
            return cls()
        keymap = {'interval': 'interval_s', 'interval_s': 'interval_s',
                  'budget_gb': 'budget_gb', 'budget': 'budget_gb',
                  'watermark': 'watermark', 'rearm': 'rearm_frac',
                  'rearm_frac': 'rearm_frac'}
        kwargs = {}
        for part in text.split(','):
            if '=' not in part:
                continue
            k, v = part.split('=', 1)
            k = keymap.get(k.strip())
            if k is None:
                continue
            try:
                kwargs[k] = float(v)
            except ValueError:
                pass
        return cls(**kwargs)

    def to_dict(self):
        return {'interval_s': self.interval_s, 'budget_gb': self.budget_gb,
                'watermark': self.watermark, 'rearm_frac': self.rearm_frac}


def resolve_memstats(arg=None):
    """The shared opt-in posture (same shape as resolve_watchdog):
    explicit False -> None (off even if the env says on); True ->
    MemConfig(); MemConfig/dict pass through; None -> the
    PADDLE_TPU_MEMSTATS env decides.  Returns a MemConfig or None."""
    if arg is False:
        return None
    if arg is None:
        return MemConfig.from_env(os.environ.get(MEMSTATS_ENV))
    if arg is True:
        return MemConfig()
    if isinstance(arg, MemConfig):
        return arg
    if isinstance(arg, dict):
        return MemConfig(**arg)
    raise TypeError(
        f'memstats= expects bool/dict/MemConfig, got {arg!r}')


def armed(arg=None):
    """True when memory extraction at the armed-only choke points
    (hapi/jit/serving/warm-start) should pay its extra compile."""
    return resolve_memstats(arg) is not None


# -- compiled truth -----------------------------------------------------------

# per-module registry behind /memory.json and the live three-way join:
# name -> the memory_compiled event's data dict (newest wins — a
# retrace replaces its module's row)
_modules = {}
_modules_lock = threading.Lock()


def reset_modules():
    """Drop the per-module registry (tests; a fresh run in-process)."""
    with _modules_lock:
        _modules.clear()


def _memory_analysis_fields(compiled):
    """CompiledMemoryStats -> plain byte fields, or None when the
    backend does not implement memory_analysis (older jaxlibs return
    None; some raise)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    fields = {}
    for key, attr in (('argument_bytes', 'argument_size_in_bytes'),
                      ('output_bytes', 'output_size_in_bytes'),
                      ('temp_bytes', 'temp_size_in_bytes'),
                      ('alias_bytes', 'alias_size_in_bytes'),
                      ('code_bytes', 'generated_code_size_in_bytes')):
        try:
            fields[key] = int(getattr(mem, attr))
        except Exception:
            fields[key] = 0
    # XLA's own peak reservation: arguments + outputs + temps, minus
    # buffers aliased between them (donation) which exist only once
    fields['compiled_peak_bytes'] = max(
        0, fields['argument_bytes'] + fields['output_bytes']
        + fields['temp_bytes'] - fields['alias_bytes'])
    return fields


def _predicted_peak(compiled, hlo_text=None):
    """The PR-4 liveness estimate over the COMPILED module's own HLO
    text, so predicted and compiled describe the same program."""
    try:
        if hlo_text is None:
            hlo_text = compiled.as_text()
        from ..analysis import hlo as _hlo
        return int(_hlo.peak_memory(_hlo.parse_module(hlo_text)))
    except Exception:
        return None


def note_compiled(name, compiled, *, source='', hlo_text=None,
                  predicted_bytes=None):
    """Extract one Compiled's memory_analysis + liveness prediction
    into a ``memory_compiled`` event and the /memory.json registry.
    FREE for callers that already hold a Compiled; never raises
    (telemetry must not be able to kill a run).  Returns the event
    data dict or None when nothing could be extracted."""
    try:
        fields = _memory_analysis_fields(compiled)
        if fields is None:
            return None
        if predicted_bytes is None:
            predicted_bytes = _predicted_peak(compiled, hlo_text)
        data = dict(name=name, source=source or 'direct', **fields)
        if predicted_bytes is not None:
            data['predicted_peak_bytes'] = int(predicted_bytes)
            if fields['compiled_peak_bytes'] > 0:
                data['ratio'] = round(
                    predicted_bytes / fields['compiled_peak_bytes'], 4)
        with _modules_lock:
            _modules[name] = dict(data)
        from . import event
        event('memory_compiled', **data)
        return data
    except Exception:
        return None


def maybe_note_compiled(name, jitted, example_args, *, source='',
                        memstats=None):
    """The ARMED extraction path for choke points that hold only a
    jitted callable: pays a fresh ``lower().compile()`` (roughly one
    extra compile, amortized by the persistent XLA cache) — so it runs
    only under PADDLE_TPU_MEMSTATS.  Never raises."""
    if not armed(memstats):
        return None
    try:
        compiled = jitted.lower(*example_args).compile()
    except Exception:
        return None
    return note_compiled(name, compiled, source=source or 'armed')


# -- live truth ---------------------------------------------------------------

def host_rss_bytes():
    """Current resident set size of this process (bytes), or None."""
    try:
        with open('/proc/self/statm') as f:
            rss_pages = int(f.read().split()[1])
        return rss_pages * os.sysconf('SC_PAGE_SIZE')
    except Exception:
        pass
    try:
        import resource
        # ru_maxrss is KiB on Linux (bytes on macOS) — high-water, not
        # current, but better than nothing where /proc is absent
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(ru * 1024)
    except Exception:
        return None


def device_memory_stats():
    """Per-device ``memory_stats()`` rows for the addressable devices,
    or None when the backend does not expose them (CPU does not)."""
    try:
        import jax
        rows = []
        for dev in jax.local_devices():
            st = dev.memory_stats()
            if st is None:
                return None
            rows.append({'device': str(dev.id),
                         'bytes_in_use': int(st.get('bytes_in_use', 0)),
                         'peak_bytes_in_use': int(
                             st.get('peak_bytes_in_use', 0)),
                         'bytes_limit': int(st.get('bytes_limit', 0))})
        return rows or None
    except Exception:
        return None


def live_arrays_bytes():
    """Total committed bytes of all live jax arrays (aval metadata
    only — no device sync, no transfer).  The CPU fallback census so
    tier-1 covers the sampler path on every backend."""
    try:
        import jax
        total = 0
        for a in jax.live_arrays():
            try:
                total += int(a.nbytes)
            except Exception:
                pass
        return total
    except Exception:
        return None


class MemorySampler:
    """Daemon thread publishing live memory truth at boundary rate.

    Each tick reads ``device.memory_stats()`` (TPU/GPU) or falls back
    to the live-arrays census (CPU), sets the
    ``memory.device_bytes`` / ``memory.device_peak_bytes`` /
    ``memory.host_rss`` gauges and emits one ``memory_sample`` event —
    the record :class:`telemetry.monitors.MemoryMonitor` fires
    ``memory_pressure`` from.  Zero per-step work, zero device syncs;
    default OFF (watchdog posture, ``PADDLE_TPU_MEMSTATS``)."""

    def __init__(self, config=None):
        self.config = config if isinstance(config, MemConfig) \
            else (resolve_memstats(config) or MemConfig())
        self._stop = threading.Event()
        self._thread = None
        self.samples = 0            # ticks taken (tests/diagnostics)
        self.last = None            # last sample dict

    def start(self):
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._run, name='paddle-tpu-memstats', daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout=2.0):
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout)

    def sample_once(self):
        """Take one sample now (also the thread's tick body)."""
        sample = {'source': None}
        rows = device_memory_stats()
        if rows is not None:
            sample['source'] = 'device_stats'
            sample['device_bytes'] = max(
                r['bytes_in_use'] for r in rows)
            sample['device_peak_bytes'] = max(
                r['peak_bytes_in_use'] for r in rows)
            limit = max(r['bytes_limit'] for r in rows)
            if limit:
                sample['device_limit_bytes'] = limit
        else:
            census = live_arrays_bytes()
            if census is not None:
                sample['source'] = 'live_arrays'
                sample['device_bytes'] = census
                prev = (self.last or {}).get('device_peak_bytes', 0)
                sample['device_peak_bytes'] = max(prev, census)
        rss = host_rss_bytes()
        if rss is not None:
            sample['host_rss'] = rss
        if sample['source'] is None and rss is None:
            return None
        budget = self.config.budget_bytes
        if budget is not None:
            sample['budget_bytes'] = budget
        self.last = sample
        self.samples += 1
        try:
            from . import event, set_gauge
            for key in ('device_bytes', 'device_peak_bytes', 'host_rss'):
                if sample.get(key) is not None:
                    set_gauge(f'memory.{key}', sample[key])
            event('memory_sample', **sample)
        except Exception:
            pass
        return sample

    def _run(self):
        while not self._stop.is_set():
            try:
                self.sample_once()
            except Exception:
                pass        # the sampler must never kill anything
            self._stop.wait(self.config.interval_s)


# process-global sampler, armed at most once (trainer fit / serving
# engine start call ensure_sampler(); default-off env keeps it None)
_sampler = None
_sampler_lock = threading.Lock()


def ensure_sampler(arg=None):
    """Start the process-global MemorySampler iff the posture says on
    (idempotent; returns the sampler or None).  The cheap call every
    run entry point makes — unset env means this is a no-op."""
    cfg = resolve_memstats(arg)
    if cfg is None:
        return None
    global _sampler
    with _sampler_lock:
        if _sampler is None:
            _sampler = MemorySampler(cfg).start()
        return _sampler


def stop_sampler():
    """Stop and drop the process-global sampler (tests, shutdown)."""
    global _sampler
    with _sampler_lock:
        s, _sampler = _sampler, None
    if s is not None:
        s.stop()
    return s


# -- the three-way join (/memory.json) ----------------------------------------

def snapshot():
    """The /memory.json document: per-module predicted vs compiled
    rows joined with the live gauges.  Plain dict of plain scalars."""
    from .recorder import get_recorder
    with _modules_lock:
        modules = {k: dict(v) for k, v in _modules.items()}
    rec = get_recorder()
    with rec._lock:
        gauges = dict(rec.gauges)
    live = {k.split('.', 1)[1]: v for k, v in gauges.items()
            if k.startswith('memory.')}
    kv = {k: v for k, v in gauges.items()
          if k in ('free_blocks', 'total_blocks', 'kv_occupancy')
          or k.startswith('kv_')}
    cfg = resolve_memstats()
    doc = {'modules': modules, 'live': live, 'kv_pool': kv,
           'armed': cfg is not None}
    if cfg is not None:
        doc['config'] = cfg.to_dict()
    return doc


def prometheus():
    """Prometheus families for the memory plane (the httpd source
    protocol's optional second surface)."""
    doc = snapshot()
    out = []
    for key, val in sorted(doc['live'].items()):
        try:
            out.append(f'# TYPE paddle_tpu_memory_{key} gauge')
            out.append(f'paddle_tpu_memory_{key} {float(val)}')
        except (TypeError, ValueError):
            pass
    for name, row in sorted(doc['modules'].items()):
        for field in ('predicted_peak_bytes', 'compiled_peak_bytes'):
            v = row.get(field)
            if v is None:
                continue
            out.append(f'# TYPE paddle_tpu_memory_{field} gauge')
            esc = str(name).replace('\\', r'\\').replace('"', r'\"')
            out.append(
                f'paddle_tpu_memory_{field}{{module="{esc}"}} {v}')
    return '\n'.join(out) + '\n'
