"""Sampled self-profiling runtime: capture N steps of on-device trace
every M steps, parse it, and close the predicted-vs-observed loop.

PRs 4/6 built a collective cost model and a calibration fitter, but
the fitter's input — ``collective_observed`` telemetry events — had no
producer: predictions rode every run, measurements rode none.  This
module is the producer:

1. **capture** — ``jax.profiler.start_trace``/``stop_trace`` around a
   small window of steps, on a :class:`ProfileSchedule` (default OFF;
   opt in per run with ``fit(profile=…)`` /
   ``ParallelTrainer(profile=…)`` or globally with the
   ``PADDLE_TPU_PROFILE`` env var);
2. **parse** — the emitted perfetto ``*.trace.json.gz`` becomes per-op
   durations (``profiler.trace``, stdlib gzip+json);
3. **match** — profiled collective ops join the compiled module's
   census by instruction name (``analysis.hlo.collective_instrs``:
   opcode + replica-group + byte signature);
4. **emit** — real ``collective_observed`` events (op, wire_bytes,
   phases, us — exactly what ``tools/calibrate_costmodel.py`` fits),
   one ``profile_capture`` event per window, and
   ``profile.*`` gauges splitting per-step device time into compute
   vs collective.

The cost contract: OUTSIDE a window, ``observe()`` is one integer
compare — no host sync, no device traffic (the PR-3 transfer-guard
proof holds with a profiler attached; tests/test_event_profile.py
holds it).  The window close pays one ``block_until_ready`` (the
window's steps must land in the trace) plus host-side parse time.

Schedule spec grammar (env var and string form)::

    PADDLE_TPU_PROFILE=1                      # defaults: 2 steps @ 10,
                                              # every 200, 4 windows
    PADDLE_TPU_PROFILE=every=100,steps=3,start=5,limit=2,dir=/tmp/p
    fit(profile=True) / fit(profile='every=50,steps=2')
    fit(profile={'every': 50, 'steps': 2})
    fit(profile=False)                        # force off, beats env
"""
import contextlib
import os
import time

from . import recorder as _rec

__all__ = ['ProfileSchedule', 'StepProfiler', 'step_profiler',
           'capture', 'resolve_schedule', 'ENV_VAR']

ENV_VAR = 'PADDLE_TPU_PROFILE'

_OFF = ('', '0', 'off', 'false', 'none', 'no')


class ProfileSchedule:
    """When to capture: ``steps``-step windows starting at ``start``
    and every ``every`` steps after, at most ``limit`` windows.
    Windows never include step 0 — the first step of a fresh compile
    measures XLA, not the model."""

    __slots__ = ('every', 'steps', 'start', 'limit', 'dir')

    def __init__(self, every=200, steps=2, start=10, limit=4,
                 dir=None):
        self.every = max(1, int(every))
        self.steps = max(1, int(steps))
        self.start = max(1, int(start))
        self.limit = max(1, int(limit))
        self.dir = dir

    def starts_at(self, step, windows_done=0):
        """True when a capture window should open at `step`."""
        if windows_done >= self.limit or step < self.start:
            return False
        return (step - self.start) % self.every == 0

    def to_dict(self):
        return {'every': self.every, 'steps': self.steps,
                'start': self.start, 'limit': self.limit}

    def __repr__(self):
        return (f'ProfileSchedule(every={self.every}, '
                f'steps={self.steps}, start={self.start}, '
                f'limit={self.limit})')

    @classmethod
    def parse(cls, spec):
        """True / 'on' → defaults; 'k=v,…' / dict → configured;
        off-ish values → None."""
        if spec is None or spec is False:
            return None
        if spec is True:
            return cls()
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, dict):
            return cls(**spec)
        s = str(spec).strip()
        if s.lower() in _OFF:
            return None
        if s.lower() in ('1', 'on', 'true', 'yes'):
            return cls()
        kw = {}
        for part in s.split(','):
            part = part.strip()
            if not part:
                continue
            if '=' not in part:
                raise ValueError(
                    f'bad {ENV_VAR} spec {spec!r}: expected '
                    "'key=value,…' with keys every/steps/start/"
                    'limit/dir')
            k, v = part.split('=', 1)
            k = k.strip()
            if k == 'dir':
                kw[k] = v.strip()
            elif k in ('every', 'steps', 'start', 'limit'):
                kw[k] = int(v)
            else:
                raise ValueError(
                    f'bad {ENV_VAR} key {k!r} in {spec!r}')
        return cls(**kw)

    @classmethod
    def from_env(cls):
        return cls.parse(os.environ.get(ENV_VAR))


def resolve_schedule(profile=None):
    """The schedule a loop should run: an explicit ``profile=`` value
    wins (``False`` forces off); ``None`` defers to the
    ``PADDLE_TPU_PROFILE`` env var — so any run can be profiled
    without a code change.  Returns a ProfileSchedule or None."""
    if profile is None:
        return ProfileSchedule.from_env()
    return ProfileSchedule.parse(profile)


class StepProfiler:
    """Drives sampled capture windows over one step loop.

    Call :meth:`observe` once per step AFTER the step's dispatch,
    passing the step index and (ideally) a device value of that step
    (``sync=loss``) so the window close can wait for the traced work
    to finish.  Call :meth:`close` at loop end — an open window is
    finalized, a pending one abandoned.

    ``hlo_text_fn`` (e.g. ``ParallelTrainer.compiled_text``) enables
    the census join: with it, every profiled collective becomes a
    ``collective_observed`` event carrying wire bytes + phases from
    the compiled module — the calibration fit input.  Without it the
    window still yields the ``profile_capture`` event and the
    compute-vs-collective breakdown gauges.

    Never raises out of observe/close: profiling is evidence, not a
    blocker — a failed capture lands as an ``error`` field on the
    ``profile_capture`` event.
    """

    def __init__(self, schedule, base_dir=None, name='train',
                 hlo_text_fn=None, mesh_shape=None, calibration=None,
                 num_partitions=None):
        self.schedule = schedule
        self.name = name
        self.hlo_text_fn = hlo_text_fn
        self.mesh_shape = dict(mesh_shape) if mesh_shape else None
        self.calibration = calibration
        self.num_partitions = num_partitions
        self.base_dir = base_dir or schedule.dir
        self.windows = []       # summary dict per closed window
        self._active = None     # {'lo': step, 'hi': step, 'dir': …}
        self._last_step = None  # newest step observe() saw
        self._observed_rows = []

    # -- directory -----------------------------------------------------------
    def _ensure_dir(self):
        if self.base_dir is None:
            import tempfile
            self.base_dir = tempfile.mkdtemp(
                prefix='paddle_tpu_profile_')
        os.makedirs(self.base_dir, exist_ok=True)
        return self.base_dir

    # -- loop hooks ----------------------------------------------------------
    def observe(self, step_no, sync=None, span=1):
        """One step just dispatched; `step_no` is its 0-based index in
        THIS loop (both wired loops count calls from 0, so schedule
        steps mean the same thing on every path — and ``start=1``, the
        smallest schedulable window, opens right after the first
        call).  Cheap outside a window (an int compare); opens the
        trace when the NEXT step starts a window, closes + parses when
        this step completed one.

        ``span=K`` (a fused chunk, core.scan_loop) declares that this
        ONE dispatch covered steps ``step_no .. step_no+K-1``: windows
        then open at exact chunk boundaries and close on whole chunks,
        so a window landing inside a fused run attributes its
        collective us to ``step_lo .. step_lo+n*K-1`` — exact step
        ids, never a blurred range."""
        try:
            span = max(1, int(span))
            last = step_no + span - 1
            self._last_step = last
            if self._active is not None:
                if last >= self._active['hi']:
                    # a chunk never splits: the window's hi stretches
                    # to this chunk's exact last step id
                    self._active['hi'] = max(self._active['hi'], last)
                    self._stop(sync)
                return
            # does a scheduled start land inside the NEXT chunk?
            for s in range(last + 1, last + span + 1):
                if self.schedule.starts_at(s, len(self.windows)):
                    # open at the chunk boundary (exact step id) and
                    # cover whole chunks
                    import math
                    n_chunks = math.ceil(self.schedule.steps / span)
                    self._start(last + 1, hi=last + n_chunks * span)
                    break
        except Exception:       # profiling must never kill the loop
            self._active = None

    def close(self, sync=None):
        """Finalize at loop end: an open window is parsed as-is."""
        try:
            if self._active is not None:
                self._stop(sync)
        except Exception:
            self._active = None

    # -- window mechanics ----------------------------------------------------
    def _start(self, lo, hi=None):
        import jax
        d = os.path.join(self._ensure_dir(),
                         f'trace-{self.name}-step{lo:06d}')
        jax.profiler.start_trace(d)
        self._active = {'lo': lo,
                        'hi': (hi if hi is not None
                               else lo + self.schedule.steps - 1),
                        'dir': d, 't0': time.perf_counter()}

    def _stop(self, sync):
        import jax
        win = self._active
        self._active = None
        err = None
        try:
            if sync is not None:
                # the traced steps run async; they must finish before
                # stop_trace or the window would be empty
                jax.block_until_ready(sync)
        except Exception:
            pass
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            err = f'stop_trace: {e!r}'
        # a close() mid-window traced fewer steps than planned — the
        # per-step breakdown must divide by what actually ran; a
        # window whose first step never ran (opened on the loop's
        # final observe) has nothing to parse at all
        ran = self._last_step is None or self._last_step >= win['lo']
        hi = win['hi'] if self._last_step is None \
            else max(win['lo'], min(win['hi'], self._last_step))
        summary = {'window': len(self.windows),
                   'step_lo': win['lo'], 'step_hi': hi,
                   'steps': hi - win['lo'] + 1,
                   'dir': win['dir'], 'name': self.name,
                   'wall_s': round(time.perf_counter() - win['t0'], 4)}
        if err is None and not ran:
            err = 'window opened but no step ran before close()'
        if err is None:
            try:
                self._parse_and_emit(win, summary)
            except Exception as e:
                err = f'parse: {e!r}'
        if err is not None:
            summary['error'] = err
        self.windows.append(summary)
        from . import event as _event
        _event('profile_capture', **summary)

    def _parse_and_emit(self, win, summary):
        from ..profiler import trace as _trace
        files = _trace.find_traces(win['dir'])
        if not files:
            summary['error'] = 'no trace file emitted'
            return
        prof = _trace.parse_trace(files[-1])
        summary['trace'] = files[-1]
        summary.update(prof.summary())
        n_steps = summary['steps']
        devices = self.num_partitions or max(1, prof.device_pids)
        per_step = prof.device_total_us / (n_steps * devices)
        coll_per_step = prof.collective_total_us / (n_steps * devices)
        summary['device_us_per_step'] = round(per_step, 3)
        summary['collective_us_per_step'] = round(coll_per_step, 3)
        summary['collective_frac'] = round(
            coll_per_step / per_step, 4) if per_step else 0.0
        from . import event as _event, set_gauge as _gauge
        # the per-step device-compute vs collective-time breakdown
        _gauge(f'profile.{self.name}.device_us_per_step',
               summary['device_us_per_step'])
        _gauge(f'profile.{self.name}.collective_us_per_step',
               summary['collective_us_per_step'])
        _gauge(f'profile.{self.name}.collective_frac',
               summary['collective_frac'])
        rows = self._match(prof)
        summary['collective_observed'] = len(rows)
        for row in rows:
            self._observed_rows.append(row)
            _event('collective_observed', step_lo=win['lo'],
                   step_hi=win['hi'], **row)

    def _match(self, prof):
        if self.hlo_text_fn is None or not prof.collectives():
            return []
        from ..analysis import hlo as _hlo
        from ..profiler import trace as _trace
        text = self.hlo_text_fn()
        if not text:
            # the loop has no census-joinable module (e.g. a fused-
            # only trainer): keep the window's breakdown, skip the
            # per-instruction join
            return []
        module = _hlo.parse_module(text)
        idx = _hlo.collective_instrs(module,
                                     mesh_shape=self.mesh_shape,
                                     calibration=self.calibration)
        return _trace.match_collectives(
            prof, idx,
            num_partitions=self.num_partitions
            or module.num_partitions,
            name=self.name)

    @property
    def observed(self):
        """All collective_observed rows emitted so far."""
        return list(self._observed_rows)


def step_profiler(profile=None, base_dir=None, name='train', **kw):
    """A StepProfiler for a loop, or None when profiling is off —
    loops guard with ``if prof is not None`` (same contract as
    ``telemetry.step_accumulator``).  ``profile=`` semantics are
    :func:`resolve_schedule`'s; under the telemetry hard kill switch
    (``PADDLE_TPU_TELEMETRY=0``) profiling is off too — there would
    be nowhere to emit the evidence."""
    if _rec.hard_off():
        return None
    sched = resolve_schedule(profile)
    if sched is None:
        return None
    if base_dir is None and sched.dir is None:
        # archive next to the flight-recorder dumps when telemetry
        # has a home; a tempdir otherwise (_ensure_dir)
        from . import flight_dir
        base_dir = flight_dir()
    return StepProfiler(sched, base_dir=base_dir, name=name, **kw)


@contextlib.contextmanager
def capture(trace_dir, name='capture', hlo_text_fn=None,
            mesh_shape=None, calibration=None, num_partitions=None,
            steps=1, sync=None):
    """One-shot capture: trace the body, then parse + match + emit
    (``profile_capture`` + ``collective_observed`` events), yielding
    the profiler so the caller can read ``prof.windows[-1]`` /
    ``prof.observed`` afterwards.  ``steps`` is how many step
    executions the body runs (normalizes the per-step breakdown);
    ``sync`` may be set on the yielded object
    (``cap.sync = loss``) for the close-side block_until_ready."""
    sched = ProfileSchedule(every=1, steps=steps, start=1, limit=1,
                            dir=trace_dir)
    prof = StepProfiler(sched, base_dir=trace_dir, name=name,
                        hlo_text_fn=hlo_text_fn, mesh_shape=mesh_shape,
                        calibration=calibration,
                        num_partitions=num_partitions)
    prof.sync = sync
    prof._start(1)
    try:
        yield prof
    finally:
        prof.close(sync=getattr(prof, 'sync', None))
