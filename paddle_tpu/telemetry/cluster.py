"""Cluster observability plane: live multi-host TRAINING telemetry.

PR 13 made the *serving* runtime observable while it runs; the
multi-host training cluster stayed post-hoc — per-rank JSONL merged by
``tools/run_report.py`` after the job exits, which is exactly when
straggler and divergence information stops being actionable.  This
module is the training-side sensor substrate (ROADMAP item 3):

* :class:`ClusterPublisher` — runs on EVERY rank.  Subscribes to the
  process Recorder's boundary-rate stream (``Recorder.subscribe`` —
  the same buffered ``steps`` flushes the exporters consume, so zero
  new device syncs and nothing per-step) and periodically overwrites
  one compact **stats frame** on the existing
  ``distributed.collective`` KV transport: rolling step-time
  percentiles, last step / last committed step, compile + retrace
  counts, predicted-vs-observed collective ratio, a loss-window
  digest, and the rolling means of any extra per-step columns the
  loop feeds its accumulator (e.g. the soak worker's
  ``compute_ms``/``coll_ms`` split).  Publishing is a non-blocking
  KV overwrite (``HostCollectives.post_stats``) — a publisher can
  never stall or kill a step.
* :class:`ClusterAggregator` — runs on rank 0 (or any observer).
  ``collect()`` non-blockingly reads every rank's latest frame plus
  the watchdog heartbeats and joins them into ONE cluster view:

  - per-rank step-time **skew** with straggler *attribution* (which
    rank, how far behind, stale heartbeat or stale frame), via
    :func:`attribute_straggler`;
  - a per-step **critical-path breakdown** — compute vs collective
    vs host-wait vs slowest-rank wait — when frames carry the
    compute/collective split;
  - a cross-rank **loss-divergence** digest (relative spread of the
    per-rank loss windows);
  - **degraded-view semantics**: a dead or hung rank's frame goes
    stale and is *marked* stale (age, last step, heartbeat age) —
    the view degrades, it never crashes.  Chaos-validated by
    tests/test_cluster_obs.py::TestClusterObsE2E (SIGKILL mid-run).

  The view is served through the PR-13 ``MetricsServer`` as
  ``/cluster/status.json`` + ``/metrics`` families
  (``MetricsServer.add_source`` — one port, serving AND cluster
  views), and attached ``telemetry.monitors`` latch typed
  ``straggler_suspect`` / ``rank_divergence`` events off it — the
  edges a future ``plan_supervisor`` consumes.

Default OFF everywhere: arm with ``ParallelTrainer(cluster_stats=…)``
or ``PADDLE_TPU_CLUSTER_STATS=1`` (off/0/unset = off; a float value
sets the publish interval in seconds).
"""
import json
import os
import threading
import time

from .live import RollingWindow
from .recorder import get_recorder

__all__ = ['ClusterPublisher', 'ClusterAggregator', 'ClusterPlane',
           'attribute_straggler', 'critical_path', 'loss_divergence',
           'resolve_cluster_stats', 'enable_cluster_plane',
           'CLUSTER_STATS_ENV', 'FRAME_VERSION']

CLUSTER_STATS_ENV = 'PADDLE_TPU_CLUSTER_STATS'
FRAME_VERSION = 1

_MONO = time.monotonic
_WALL = time.time


def resolve_cluster_stats(arg=None):
    """The shared opt-in posture (mirrors ``resolve_watchdog`` /
    ``resolve_metrics_port``): explicit ``False`` -> None (off even if
    the env says on); ``True`` -> default interval; a number -> that
    publish interval in seconds; ``None`` -> the
    PADDLE_TPU_CLUSTER_STATS env decides (unset/'0'/'off'/'false' =
    off, '1'/'on' = default, a float = interval).  Returns the publish
    interval in seconds, or None for off."""
    if arg is False:
        return None
    if arg is True:
        return 2.0
    if arg is not None:
        return float(arg)
    text = (os.environ.get(CLUSTER_STATS_ENV) or '').strip().lower()
    if text in ('', '0', 'off', 'false'):
        return None
    if text in ('1', 'on', 'true'):
        return 2.0
    try:
        return float(text)
    except ValueError:
        return None


def _median(vals):
    """Proper even-count median (a 2-rank cluster must not anchor a
    baseline on the slower rank).  None for an empty input.
    tools/run_report.py carries its own copy on purpose: it must run
    stdlib-only on a machine with no paddle_tpu install."""
    if not vals:
        return None
    vs = sorted(vals)
    n = len(vs)
    return vs[n // 2] if n % 2 else 0.5 * (vs[n // 2 - 1] + vs[n // 2])


def _transport(transport=None, client=None, rank=None, world=None,
               namespace='ptpu'):
    from ..distributed.collective import HostCollectives
    if transport is not None:
        return transport
    return HostCollectives(client=client, rank=rank, world=world,
                           namespace=namespace)


class ClusterPublisher:
    """One rank's side of the plane: fold the boundary-rate event
    stream into rolling windows and periodically overwrite this rank's
    stats frame on the KV transport.

        pub = ClusterPublisher(transport=hc, interval_s=2.0).install()
        ...train...          # frames publish at steps-flush cadence
        pub.uninstall()

    Publishing triggers from inside the subscriber callback — i.e. at
    the Recorder's boundary rate (steps flushes, compiles, checkpoint
    events), never per step — and is rate-limited to ``interval_s``.
    With no KV client the publisher still aggregates (``frame()``
    works) but ``publish()`` is a no-op."""

    def __init__(self, transport=None, client=None, rank=None,
                 world=None, namespace='ptpu', interval_s=2.0,
                 window_s=60.0, recorder=None):
        self.transport = _transport(transport, client, rank, world,
                                    namespace)
        self.rank = self.transport.rank
        self.interval_s = float(interval_s)
        self.window_s = float(window_s)
        self._lock = threading.RLock()
        self._recorder = recorder
        self._installed = False
        # rolling state (all host-side floats; fed from flushed rows).
        # write() runs on whatever thread emitted the event, so every
        # mutable field below belongs to _lock (the concurrency lint
        # enforces the annotations).
        self.step_ms = RollingWindow(window_s)      # guarded-by: _lock
        self.wait_ms = RollingWindow(window_s)      # guarded-by: _lock
        self.loss = RollingWindow(window_s)         # guarded-by: _lock
        self.cols = {}                              # guarded-by: _lock
        self.coll_ratio = RollingWindow(window_s)   # guarded-by: _lock
        self.last_step = None                       # guarded-by: _lock
        self.last_commit_step = None                # guarded-by: _lock
        self.steps_total = 0                        # guarded-by: _lock
        self.compiles = 0                           # guarded-by: _lock
        self.compile_s = 0.0                        # guarded-by: _lock
        self.retraces = 0                           # guarded-by: _lock
        self.tag = None                             # guarded-by: _lock
        self._seq = 0                               # guarded-by: _lock
        self._last_pub = 0.0                        # guarded-by: _lock
        self.published = 0                          # guarded-by: _lock

    # -- lifecycle -----------------------------------------------------------
    def install(self, recorder=None):
        rec = recorder or self._recorder or get_recorder()
        if not self._installed:
            rec.subscribe(self.write)
            self._recorder = rec
            self._installed = True
        return self

    def uninstall(self):
        if self._installed and self._recorder is not None:
            self._recorder.unsubscribe(self.write)
            self._installed = False
        return self

    def close(self):                    # writer-protocol compatibility
        self.uninstall()

    # -- stream consumption ---------------------------------------------------
    def write(self, rec):
        """Route one boundary-rate record; maybe publish.  Never
        raises (the Recorder swallows subscriber exceptions, but a
        publisher bug must not even cost the swallow)."""
        try:
            kind = rec.get('kind')
            now = _MONO()
            with self._lock:
                if kind == 'steps':
                    self._on_steps(rec, now)
                elif kind == 'compile':
                    self.compiles += 1
                    self.compile_s += rec.get('dur_s') or 0.0
                elif kind == 'retrace':
                    self.retraces += 1
                elif kind == 'collective_observed':
                    us, pred = rec.get('us'), rec.get('predicted_us')
                    if us and pred:
                        self.coll_ratio.add(us / pred, now)
                elif kind in ('checkpoint_commit', 'checkpoint_save'):
                    step = rec.get('step')
                    if step is not None:
                        self.last_commit_step = step
            self.maybe_publish(now)
        except Exception:
            pass

    def _on_steps(self, rec, now):      # locked-by: _lock
        self.tag = rec.get('tag', self.tag)
        n = rec.get('n') or 0
        self.steps_total += n
        hi = rec.get('step_hi')
        if hi is not None:
            self.last_step = (hi if self.last_step is None
                              else max(self.last_step, hi))
        for t in rec.get('step_time_ms') or ():
            if t is not None:
                self.step_ms.add(t, now)
        for w in rec.get('wait_ms') or ():
            if w is not None:
                self.wait_ms.add(w, now)
        for k, col in rec.items():
            if k in ('kind', 'ts', 't', 'rank', 'tag', 'n', 'step',
                     'step_lo', 'step_hi', 'step_time_ms', 'wait_ms'):
                continue
            if not isinstance(col, list):
                continue
            win = (self.loss if k == 'loss' else
                   self.cols.setdefault(k, RollingWindow(self.window_s)))
            for v in col:
                if v is not None:
                    win.add(v, now)

    # -- frames --------------------------------------------------------------
    def frame(self, now=None):
        """This rank's current stats frame (a plain JSON-able dict)."""
        now = now if now is not None else _MONO()
        with self._lock:
            self._seq += 1

            def _mean(win):
                vals = win.values(now)
                return round(sum(vals) / len(vals), 4) if vals else None

            pct = self.step_ms.percentiles(now)
            doc = {
                'v': FRAME_VERSION,
                'rank': self.rank,
                'seq': self._seq,
                'ts': _WALL(),
                'tag': self.tag,
                'step': self.last_step,
                'last_commit_step': self.last_commit_step,
                'steps_total': self.steps_total,
                'step_ms': {k: round(v, 4) if k != 'count' else v
                            for k, v in pct.items()},
                'wait_ms_mean': _mean(self.wait_ms),
                'compiles': self.compiles,
                'compile_s': round(self.compile_s, 4),
                'retraces': self.retraces,
                'coll_ratio': _mean(self.coll_ratio),
                'cols': {k: m for k, m in
                         ((k, _mean(w)) for k, w in self.cols.items())
                         if m is not None},
            }
            vals = self.loss.values(now)
            if vals:
                doc['loss'] = {'last': round(vals[-1], 6),
                               'mean': round(sum(vals) / len(vals), 6),
                               'count': len(vals)}
        # per-rank memory columns (memory observatory): the sampler's
        # last gauges, read at frame rate — absent when the sampler is
        # off, so frames stay byte-compatible with the pre-memory wire
        try:
            gauges = get_recorder().gauges
            for field, key in (('mem_device_bytes', 'memory.device_bytes'),
                               ('mem_peak_bytes',
                                'memory.device_peak_bytes'),
                               ('mem_host_rss', 'memory.host_rss')):
                v = gauges.get(key)
                if v is not None:
                    doc[field] = int(v)
        except Exception:
            pass
        return doc

    def maybe_publish(self, now=None):
        now = now if now is not None else _MONO()
        with self._lock:
            if now - self._last_pub < self.interval_s:
                return False
            # claim the slot BEFORE posting: write() runs on every
            # emitter thread, and an unlocked check-then-act here let
            # two threads pass the rate gate and double-post the frame
            self._last_pub = now
        return self._post(now)

    def publish(self, now=None):
        """Build + post one frame now (rate limit bypassed)."""
        now = now if now is not None else _MONO()
        with self._lock:
            self._last_pub = now
        return self._post(now)

    def _post(self, now):
        # the KV post runs UNLOCKED — a network RTT under _lock would
        # stall every event emitter behind the subscriber callback
        ok = self.transport.post_stats(self.frame(now))
        if ok:
            with self._lock:
                self.published += 1
        return ok


# -- pure attribution / breakdown helpers (unit-testable) ---------------------

def attribute_straggler(per_rank, skew_threshold=1.75,
                        behind_threshold=2, hb_stale_s=None):
    """Who is holding the cluster back, and why.

    ``per_rank``: {rank: row} where each row may carry ``compute_ms``
    (pre-collective host/device work — the discriminating signal in a
    BSP step, where the *total* step time equalizes through the
    collective barrier), ``step_p50_ms``, ``step`` (last step id),
    ``stale`` (frame stale flag) and ``hb_age_s``.

    Returns ``{'rank', 'skew', 'behind', 'cause', 'hb_stale'}`` or
    None.  Causes, in precedence order:

    * ``compute_skew`` — one rank's rolling compute time is
      ``skew_threshold`` x the median of its PEERS (leave-one-out:
      with a median over all ranks a 2-rank cluster could never
      exceed 2x however slow the straggler) — the throttled-rank
      signature: every peer's *collective wait* inflates equally,
      but only the straggler's *compute* does;
    * ``step_skew`` — same test on total step time (no split
      available; still catches non-lockstep loops);
    * ``behind`` — a rank's last published step trails the cluster
      max by ``behind_threshold`` steps or more;
    * ``stale`` — a rank stopped publishing (frame stale / missing)
      while peers progressed: dead or hung."""
    if not per_rank:
        return None

    def _skew_on(field):
        vals = {r: row.get(field) for r, row in per_rank.items()
                if not row.get('stale') and row.get(field) is not None}
        if len(vals) < 2:
            return None
        worst = max(vals, key=lambda r: vals[r])
        # leave-one-out baseline: the median of the candidate's PEERS
        base = _median([v for r, v in vals.items() if r != worst])
        skew = vals[worst] / max(base, 1e-9)
        return (worst, round(skew, 4)) if skew >= skew_threshold \
            else None

    steps = [row.get('step') for row in per_rank.values()
             if row.get('step') is not None]
    max_step = max(steps) if steps else None

    def _result(rank, cause, skew=None):
        row = per_rank[rank]
        behind = (max_step - row['step']
                  if max_step is not None and row.get('step') is not None
                  else None)
        hb = row.get('hb_age_s')
        return {'rank': rank, 'cause': cause, 'skew': skew,
                'behind': behind,
                'hb_age_s': hb,
                'hb_stale': (hb is not None and hb_stale_s is not None
                             and hb > hb_stale_s)}

    hit = _skew_on('compute_ms')
    if hit:
        return _result(hit[0], 'compute_skew', hit[1])
    hit = _skew_on('step_p50_ms')
    if hit:
        return _result(hit[0], 'step_skew', hit[1])
    if max_step is not None:
        laggards = {r: max_step - row['step']
                    for r, row in per_rank.items()
                    if row.get('step') is not None
                    and max_step - row['step'] >= behind_threshold}
        if laggards:
            worst = max(laggards, key=lambda r: laggards[r])
            return _result(worst, 'behind')
    stale = [r for r, row in per_rank.items() if row.get('stale')]
    if stale and len(stale) < len(per_rank):
        # peers progressed while this rank went quiet
        return _result(stale[0], 'stale')
    return None


def critical_path(per_rank):
    """The cluster's per-step critical-path breakdown from the
    per-rank rows: the step is paced by the SLOWEST rank's compute,
    then the wire, and every faster rank's extra collective time is
    time spent *waiting on the straggler*.

    * ``compute_ms``   — max over ranks (the pacing rank's work);
    * ``collective_ms`` — min over ranks (the last-to-arrive rank
      waits least: its collective time is closest to pure wire);
    * ``straggler_wait_ms`` — max minus min collective time (what the
      fastest ranks burn waiting);
    * ``host_wait_ms`` — max input-pipeline wait;
    * ``step_ms``      — max rolling p50 step time.

    Components a deployment's frames don't carry are simply absent."""
    rows = [r for r in per_rank.values() if not r.get('stale')]
    if not rows:
        return {}

    def _vals(field):
        return [r[field] for r in rows if r.get(field) is not None]

    out = {}
    steps = _vals('step_p50_ms')
    if steps:
        out['step_ms'] = round(max(steps), 4)
    comp = _vals('compute_ms')
    if comp:
        out['compute_ms'] = round(max(comp), 4)
    coll = _vals('coll_ms')
    if coll:
        out['collective_ms'] = round(min(coll), 4)
        if len(coll) > 1:
            out['straggler_wait_ms'] = round(max(coll) - min(coll), 4)
    waits = _vals('wait_ms_mean')
    if waits:
        out['host_wait_ms'] = round(max(waits), 4)
    return out


def loss_divergence(per_rank, band=0.25):
    """Cross-rank loss-divergence digest: the relative spread of the
    per-rank rolling loss means.  In data-parallel SPMD the post-sync
    loss is identical on every rank — any sustained spread means a
    rank is training on different state (corrupt restore, a collective
    fault that leaked, a desynced rng stream)."""
    losses = {r: row.get('loss_mean') for r, row in per_rank.items()
              if not row.get('stale') and row.get('loss_mean') is not None}
    if len(losses) < 2:
        return None
    vals = sorted(losses.values())
    med = vals[len(vals) // 2]
    scale = max(abs(med), 1e-9)
    spread = (vals[-1] - vals[0]) / scale
    return {'spread': round(spread, 6),
            'divergent': spread > band,
            'band': band,
            'per_rank': {r: round(v, 6) for r, v in sorted(losses.items())}}


class ClusterAggregator:
    """Rank 0's join of every rank's stats frames into one live
    cluster view.

        agg = ClusterAggregator(transport=hc, world=8)
        agg.snapshot()      # the /cluster/status.json document
        agg.prometheus()    # /metrics families

    ``collect()`` is purely non-blocking (``read_all_stats`` +
    heartbeat reads); a missing, torn, or stale frame degrades the
    view (rank marked ``stale`` with its last-seen evidence) and can
    never raise out of a scrape.  Attached monitors'
    ``observe_cluster(view)`` hooks run after every collect — that is
    where ``straggler_suspect`` / ``rank_divergence`` latch."""

    def __init__(self, transport=None, client=None, rank=None,
                 world=None, namespace='ptpu', stale_after_s=6.0,
                 skew_threshold=1.75, behind_threshold=2,
                 divergence_band=0.25, min_collect_gap_s=0.1,
                 clock_tolerance_s=30.0):
        self.transport = _transport(transport, client, rank, world,
                                    namespace)
        self.world = self.transport.world
        self.stale_after_s = float(stale_after_s)
        # wall-clock staleness fallback bound: catches a frame that
        # was ALREADY ancient when this aggregator first saw it
        # (aggregator restart next to a dead rank) without letting
        # ordinary NTP offset false-mark healthy hosts
        self.clock_tolerance_s = max(float(clock_tolerance_s),
                                     self.stale_after_s)
        self.skew_threshold = float(skew_threshold)
        self.behind_threshold = int(behind_threshold)
        self.divergence_band = float(divergence_band)
        self.min_collect_gap_s = float(min_collect_gap_s)
        # Mutable aggregator state below is guarded by _lock: collect()
        # may be called from a scrape thread (httpd handler) while a
        # monitor attaches from the trainer thread.
        self.monitors = []              # guarded-by: _lock
        self._lock = threading.RLock()
        self._last_view = None          # guarded-by: _lock
        self._last_collect = 0.0        # guarded-by: _lock
        self._t0 = _MONO()
        # staleness is judged on THIS process's monotonic clock: a
        # rank is stale when its frame seq has not advanced for
        # stale_after_s of observation time.  Comparing the frame's
        # wall-clock ts against ours would falsely stale-mark every
        # healthy rank on a host whose clock is offset by more than
        # stale_after_s (pods give no NTP guarantee — the same reason
        # run_report anchors per-host clock skew).
        self._seen = {}  # rank -> [seq, first_seen_mono]  # guarded-by: _lock

    def attach_monitor(self, monitor):
        with self._lock:
            self.monitors.append(monitor)
        return monitor

    # -- the join ------------------------------------------------------------
    def collect(self, now=None):
        """Read every rank's latest frame + heartbeat and rebuild the
        view.  Rate-limited to ``min_collect_gap_s`` (a scrape storm
        re-reads cached state).  Never raises."""
        now = now if now is not None else _MONO()
        with self._lock:
            if (self._last_view is not None
                    and now - self._last_collect < self.min_collect_gap_s):
                return self._last_view
            try:
                view = self._build_view()
            except Exception as e:      # a scrape must never crash
                view = {'v': FRAME_VERSION, 'error': repr(e)[:200],
                        'world': self.world, 'ranks': {},
                        'degraded': True}
            self._last_view = view
            self._last_collect = now
            monitors = list(self.monitors)
        for m in monitors:
            try:
                m.observe_cluster(view)
            except Exception:
                pass                    # observers never block
        self._maybe_probe_divergence(view)
        return view

    def _maybe_probe_divergence(self, view):
        """On the rank_divergence edge, diff the collective rings
        once (latched until the spread re-enters its band): if the
        divergence came from a leaked/mismatched collective, the
        ``collective_mismatch`` event names the call site.  Rank 0
        only; never raises."""
        div = (view or {}).get('loss_divergence') or {}
        if not div.get('divergent'):
            self._div_probed = False
            return
        if getattr(self, '_div_probed', False):
            return
        self._div_probed = True
        if getattr(self.transport, 'rank', 0) != 0:
            return
        try:
            from ..distributed.collective import probe_mismatch
            probe_mismatch(self.transport, trigger='rank_divergence')
        except Exception:
            pass

    def _build_view(self):  # locked-by: _lock
        wall = _WALL()
        frames = {}
        try:
            frames = self.transport.read_all_stats()
        except Exception:
            pass
        try:
            heartbeats = self.transport.read_heartbeats()
        except Exception:
            heartbeats = {}
        per_rank, missing, stale = {}, [], []
        for r in range(self.world):
            f = frames.get(r)
            if not isinstance(f, dict) or f.get('v') != FRAME_VERSION:
                missing.append(r)
                row = {'stale': True, 'missing': True}
                hb = heartbeats.get(r)
                if hb is not None:
                    row['hb_age_s'] = round(hb, 3)
                per_rank[r] = row
                continue
            # age = how long THIS observer has seen the same seq
            # (clock-offset-immune); a frame may also self-declare
            # publisher-side age for display via its ts, but the
            # staleness DECISION never trusts a remote wall clock
            now_mono = _MONO()
            seen = self._seen.get(r)
            if seen is None or seen[0] != f.get('seq'):
                self._seen[r] = seen = [f.get('seq'), now_mono]
            age = now_mono - seen[1]
            wall_age = wall - (f.get('ts') or 0)
            if wall_age > self.clock_tolerance_s:
                is_stale = True
                age = max(age, wall_age)
            else:
                is_stale = age > self.stale_after_s
            if is_stale:
                stale.append(r)
            pct = f.get('step_ms') or {}
            cols = f.get('cols') or {}
            loss = f.get('loss') or {}
            row = {
                'seq': f.get('seq'),
                'age_s': round(age, 3),
                'stale': is_stale,
                'tag': f.get('tag'),
                'step': f.get('step'),
                'last_commit_step': f.get('last_commit_step'),
                'steps_total': f.get('steps_total'),
                'step_p50_ms': pct.get('p50'),
                'step_p99_ms': pct.get('p99'),
                'step_mean_ms': pct.get('mean'),
                'wait_ms_mean': f.get('wait_ms_mean'),
                'compiles': f.get('compiles'),
                'retraces': f.get('retraces'),
                'coll_ratio': f.get('coll_ratio'),
                'loss_mean': loss.get('mean'),
                'loss_last': loss.get('last'),
                'mem_device_bytes': f.get('mem_device_bytes'),
                'mem_peak_bytes': f.get('mem_peak_bytes'),
                'mem_host_rss': f.get('mem_host_rss'),
            }
            for k, v in cols.items():
                row.setdefault(k, v)
            hb = heartbeats.get(r)
            if hb is not None:
                row['hb_age_s'] = round(hb, 3)
            per_rank[r] = row
        steps = [row.get('step') for row in per_rank.values()
                 if row.get('step') is not None]
        max_step = max(steps) if steps else None
        # per-rank skew vs the cluster median step p50 (rendered even
        # when no rank crosses the straggler threshold)
        med_p50 = _median([row['step_p50_ms']
                           for row in per_rank.values()
                           if row.get('step_p50_ms') is not None
                           and not row.get('stale')])
        for r, row in per_rank.items():
            if max_step is not None and row.get('step') is not None:
                row['behind'] = max_step - row['step']
            if med_p50 and row.get('step_p50_ms') is not None:
                row['skew'] = round(row['step_p50_ms'] / med_p50, 4)
        # memory skew (memory observatory): per-rank live bytes vs the
        # cluster median — a rank running hot on HBM is the next OOM
        med_mem = _median([row['mem_device_bytes']
                           for row in per_rank.values()
                           if row.get('mem_device_bytes')
                           and not row.get('stale')])
        for r, row in per_rank.items():
            if med_mem and row.get('mem_device_bytes'):
                row['mem_skew'] = round(
                    row['mem_device_bytes'] / med_mem, 4)
        straggler = attribute_straggler(
            per_rank, skew_threshold=self.skew_threshold,
            behind_threshold=self.behind_threshold,
            hb_stale_s=self.stale_after_s)
        div = loss_divergence(per_rank, band=self.divergence_band)
        # collective flight recorder join: per-rank ring heads + the
        # cross-rank diff (non-blocking cledger reads; absent when the
        # ledger is off or no rank has published a ring yet)
        coll = None
        try:
            from ..distributed.collective import (
                LEDGER_KEY, diff_ledgers)
            led = self.transport.read_all_stats(key=LEDGER_KEY)
            if led:
                coll = {'ranks': {
                    str(r): {'seq': f.get('seq'),
                             'step': f.get('step'),
                             'last': (f.get('entries') or [None])[-1]}
                    for r, f in sorted(led.items())}}
                d = diff_ledgers(led)
                if d is not None:
                    coll['diff'] = d
        except Exception:
            coll = None
        view = {
            'v': FRAME_VERSION,
            'ts': round(wall, 3),
            'uptime_s': round(_MONO() - self._t0, 3),
            'world': self.world,
            'max_step': max_step,
            'ranks': {str(r): row for r, row in sorted(per_rank.items())},
            'missing': missing,
            'stale': stale,
            'degraded': bool(missing or stale),
            'straggler': straggler,
            'critical_path': critical_path(per_rank),
            'loss_divergence': div,
            'collectives': coll,
        }
        return view

    # -- reads (httpd source protocol: snapshot + prometheus) ----------------
    def snapshot(self, now=None):
        return self.collect(now)

    def prometheus(self, now=None):
        """The cluster families for /metrics (``paddle_tpu_cluster_``
        prefix; rank-labelled gauges)."""
        view = self.collect(now)
        out = []

        def fam(name, mtype, help_, rows):
            emitted = False
            for labels, value in rows:
                if value is None:
                    continue
                if not emitted:
                    out.append(f'# HELP paddle_tpu_cluster_{name} '
                               f'{help_}')
                    out.append(f'# TYPE paddle_tpu_cluster_{name} '
                               f'{mtype}')
                    emitted = True
                lbl = ('{' + ','.join(
                    f'{k}="{v}"' for k, v in sorted(labels.items()))
                    + '}') if labels else ''
                out.append(f'paddle_tpu_cluster_{name}{lbl} {value}')

        ranks = view.get('ranks', {})
        fam('world_size', 'gauge', 'configured cluster world size',
            [({}, view.get('world'))])
        fam('max_step', 'gauge', 'highest step any rank published',
            [({}, view.get('max_step'))])
        fam('degraded', 'gauge',
            '1 when any rank frame is missing or stale',
            [({}, int(bool(view.get('degraded'))))])
        fam('rank_step', 'gauge', 'last step each rank published',
            [({'rank': r}, row.get('step'))
             for r, row in ranks.items()])
        fam('rank_behind', 'gauge',
            'steps each rank trails the cluster max',
            [({'rank': r}, row.get('behind'))
             for r, row in ranks.items()])
        fam('rank_step_p50_ms', 'gauge',
            'rolling p50 step time per rank (ms)',
            [({'rank': r}, row.get('step_p50_ms'))
             for r, row in ranks.items()])
        fam('rank_skew', 'gauge',
            'rank step-time p50 over the cluster median',
            [({'rank': r}, row.get('skew'))
             for r, row in ranks.items()])
        fam('rank_stale', 'gauge',
            '1 when the rank frame is older than stale_after_s',
            [({'rank': r}, int(bool(row.get('stale'))))
             for r, row in ranks.items()])
        fam('rank_frame_age_s', 'gauge', 'stats frame age per rank',
            [({'rank': r}, row.get('age_s'))
             for r, row in ranks.items()])
        fam('rank_hb_age_s', 'gauge',
            'watchdog heartbeat age per rank',
            [({'rank': r}, row.get('hb_age_s'))
             for r, row in ranks.items()])
        fam('rank_compiles', 'counter', 'compile events per rank',
            [({'rank': r}, row.get('compiles'))
             for r, row in ranks.items()])
        fam('rank_loss_mean', 'gauge',
            'rolling loss-window mean per rank',
            [({'rank': r}, row.get('loss_mean'))
             for r, row in ranks.items()])
        fam('rank_mem_device_bytes', 'gauge',
            'live device bytes per rank (memory sampler)',
            [({'rank': r}, row.get('mem_device_bytes'))
             for r, row in ranks.items()])
        fam('rank_mem_host_rss_bytes', 'gauge',
            'host RSS per rank (memory sampler)',
            [({'rank': r}, row.get('mem_host_rss'))
             for r, row in ranks.items()])
        fam('rank_mem_skew', 'gauge',
            'rank live device bytes over the cluster median',
            [({'rank': r}, row.get('mem_skew'))
             for r, row in ranks.items()])
        strag = view.get('straggler')
        fam('straggler_rank', 'gauge',
            'attributed straggler rank (-1 when none)',
            [({}, strag['rank'] if strag else -1)])
        if strag:
            fam('straggler_skew', 'gauge',
                "the attributed straggler's skew factor",
                [({}, strag.get('skew'))])
        cp = view.get('critical_path') or {}
        fam('critical_path_ms', 'gauge',
            'per-step critical-path component (ms)',
            [({'component': k.replace('_ms', '')}, v)
             for k, v in sorted(cp.items())])
        div = view.get('loss_divergence')
        if div:
            fam('loss_spread', 'gauge',
                'relative cross-rank loss-window spread',
                [({}, div.get('spread'))])
        return '\n'.join(out) + '\n'


class ClusterPlane:
    """One process's handle on the whole plane: the publisher (every
    rank), plus — on the aggregating rank — the aggregator, its
    monitors, and the HTTP source registration.  ``close()`` tears all
    of it down (idempotent)."""

    def __init__(self, publisher=None, aggregator=None, server=None,
                 owns_server=False):
        self.publisher = publisher
        self.aggregator = aggregator
        self.server = server
        self.owns_server = owns_server

    @property
    def port(self):
        return self.server.port if self.server is not None else None

    def close(self):
        if self.publisher is not None:
            try:
                # flush the final frame: a short run (or an interval
                # longer than the tail of the job) must not leave the
                # cluster view showing pre-warmup state forever
                self.publisher.publish()
            except Exception:
                pass
            self.publisher.uninstall()
            self.publisher = None
        if self.server is not None:
            try:
                if self.owns_server:
                    self.server.stop()
                else:
                    self.server.remove_source('cluster')
            except Exception:
                pass
            self.server = None
        self.aggregator = None


def enable_cluster_plane(transport=None, client=None, rank=None,
                         world=None, namespace='ptpu', interval_s=2.0,
                         window_s=60.0, aggregate=None, serve=None,
                         port=None, stale_after_s=None, monitors=True):
    """Wire the whole plane for this process:

    * every rank: a :class:`ClusterPublisher` subscribed to the global
      Recorder;
    * the aggregating rank (``aggregate=None`` -> rank 0): a
      :class:`ClusterAggregator` with ``straggler_suspect`` /
      ``rank_divergence`` monitors attached, registered as the
      ``cluster`` source on a :class:`telemetry.httpd.MetricsServer`
      — an already-running server in this process is reused (one
      port for serving + cluster views); otherwise one is started
      when a port resolves (``port=`` / PADDLE_TPU_METRICS_PORT;
      ``serve=False`` skips HTTP entirely).

    Returns a :class:`ClusterPlane` (``plane.close()`` to tear down).
    """
    tr = _transport(transport, client, rank, world, namespace)
    plane = ClusterPlane(
        publisher=ClusterPublisher(transport=tr,
                                   interval_s=interval_s,
                                   window_s=window_s).install())
    is_agg = (tr.rank == 0) if aggregate is None else bool(aggregate)
    if not is_agg:
        return plane
    kwargs = {}
    if stale_after_s is not None:
        kwargs['stale_after_s'] = stale_after_s
    agg = ClusterAggregator(transport=tr, **kwargs)
    if monitors:
        from .monitors import SLOMonitor, DriftMonitor
        agg.attach_monitor(SLOMonitor())
        agg.attach_monitor(DriftMonitor())
    plane.aggregator = agg
    if serve is False:
        return plane
    from .httpd import attach_source, resolve_metrics_port
    if serve is True and port is None:
        resolved = 0                    # force HTTP: ephemeral port
    else:
        resolved = resolve_metrics_port(port)
    try:
        server, created = attach_source('cluster', agg, port=resolved)
    except Exception:
        server, created = None, False
    plane.server = server
    plane.owns_server = created
    return plane
