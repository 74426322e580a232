"""Straggler/hang supervision: deadline budgets, heartbeat quorum,
and the timeout -> flight-dump -> coordinated-abort -> elastic-restart
escalation path.

A hung rank is the one failure the rest of the resilience stack cannot
see: a SIGKILL leaves a corpse the supervisor restarts, a NaN trips the
sentinel, but a rank stuck inside a collective just... waits, and every
peer waits with it — a deadlocked cluster that burns its reservation
until an operator notices.  The watchdog turns that into a bounded,
attributed, restartable event:

* **Budget** — per-step and per-collective deadline budgets.  Defaults
  derive from the PR-6 cost-model estimate x a slack factor when a plan
  or census estimate exists (``Budget.from_costmodel``); the
  ``PADDLE_TPU_WATCHDOG`` env (``1`` or ``step=30,collective=5,
  slack=8``) configures it fleet-wide.  Off unless explicitly enabled —
  ``ParallelTrainer(watchdog=True)`` or the env.
* **Watchdog** — a daemon thread that (a) tracks the main loop's step
  deadline (``step_started``/``step_finished`` are two attribute
  writes: nothing on the step path blocks or syncs), (b) publishes a
  per-rank heartbeat into the cluster KV store and checks peers' ages
  (a slow peer -> ``straggler`` event with rank attribution; a majority
  gone -> ``quorum_lost``), and (c) on a blown deadline escalates:
  ``timeout`` telemetry event -> flight-recorder dump -> cluster abort
  flag (peers waiting in host collectives raise CoordinatedAbort
  within one poll instead of burning their own timeouts) -> process
  exit with ``WATCHDOG_EXIT_CODE`` so distributed.elastic restarts the
  rank as ONE failure restart — never a deadlock.  The exit is
  ``os._exit``: the main thread is by definition stuck (possibly
  inside XLA, uninterruptible), and a watchdog that politely raises in
  its own thread frees nothing.
* **collective_budget** — a thread-local deadline scope the host
  transport (and anything else doing bounded cluster waits) arms;
  ``resilience.retry(deadline=)`` clamps to the remaining budget so a
  retry loop INSIDE a collective deadline cannot outlive it.
"""
import contextlib
import json
import os
import threading
import time

__all__ = ['WATCHDOG_EXIT_CODE', 'WATCHDOG_ENV', 'Budget', 'Watchdog',
           'collective_budget', 'remaining_budget', 'resolve_watchdog',
           'default_collective_s']

# distinct from PREEMPTED_EXIT_CODE (117, free restart): a watchdog
# kill IS a failure — a hung rank must cost one restart from the
# budget, or a deterministic hang restarts forever
WATCHDOG_EXIT_CODE = int(os.environ.get(
    'PADDLE_TPU_WATCHDOG_EXIT_CODE', '121'))
WATCHDOG_ENV = 'PADDLE_TPU_WATCHDOG'


class Budget:
    """Deadline budgets for one supervised loop.

    step_s        wall-clock allowance for one host-loop step (dispatch
                  to dispatch).  None -> default_step_s.
                  Under a FUSED K-step loop (core.scan_loop) one
                  host-visible "step" is a whole K-chunk: the trainer
                  passes ``step_started(budget_s=K x step_s)`` so the
                  budget covers the chunk, and — when an explicit
                  step_s was armed together with a cost-model step
                  estimate — K itself clamps so a hung chunk is still
                  detected inside the armed deadline
                  (``scan_loop.clamp_chunk`` /
                  ``ParallelTrainer.fused_chunk_len``).
    collective_s  allowance for one host collective's wait.
    slack         multiplier applied to cost-model estimates when
                  deriving budgets (estimates are ideal-wire numbers;
                  real steps pay host work, stragglers, fs jitter).
    first_step_s  allowance for the first step (compile rides on it).
    straggler_frac  fraction of step_s after which a still-running
                  step emits a ``straggler`` event (soft warning
                  before the hard timeout).
    """

    def __init__(self, step_s=None, collective_s=None, slack=8.0,
                 first_step_s=None, straggler_frac=0.5,
                 default_step_s=60.0, grace_s=5.0):
        self.step_s = None if step_s is None else float(step_s)
        self.collective_s = (None if collective_s is None
                             else float(collective_s))
        self.slack = float(slack)
        self.first_step_s = (None if first_step_s is None
                             else float(first_step_s))
        self.straggler_frac = float(straggler_frac)
        self.default_step_s = float(default_step_s)
        self.grace_s = float(grace_s)
        # where step_s came from — the adaptation ladder: an operator's
        # EXPLICIT deadline is never overridden; the analytic
        # cost-model estimate and the global default both yield to a
        # MEASURED rolling profile once one exists (note_measured)
        self.step_source = ('explicit' if step_s is not None
                            else 'default')

    def effective_step_s(self):
        return self.step_s if self.step_s is not None \
            else self.default_step_s

    def effective_first_step_s(self):
        if self.first_step_s is not None:
            return self.first_step_s
        # compile dominates the first step; be generous but bounded
        return max(120.0, 4 * self.effective_step_s())

    # -- serving SLO derivations (telemetry.monitors / serving) -----------
    def ttft_budget_s(self):
        """The aggregate TTFT allowance the serving SLO monitor
        compares its rolling p99 against: queueing + prefill ride on
        the first-step allowance, exactly like the per-request
        deadline derivation — one budget machinery, two consumers."""
        return self.effective_first_step_s()

    def request_budget_s(self, max_new_tokens, span=1):
        """Per-request completion allowance: first-step (prefill +
        compile headroom) plus one step allowance per fused decode
        span.  ``ServingEngine.request_deadline_s`` derives per-request
        deadlines from this; ``SLOMonitor`` uses the same numbers as
        aggregate thresholds."""
        import math
        spans = math.ceil(max(1, int(max_new_tokens) - 1)
                          / max(1, int(span)))
        return self.effective_first_step_s() \
            + spans * self.effective_step_s()

    @classmethod
    def from_costmodel(cls, est_step_us, slack=8.0, min_step_s=5.0,
                       **kwargs):
        """Derive the step budget from a cost-model estimate (the
        planner's ``est_us + compute_us``, or a census total): budget =
        max(min_step_s, est * slack).  The estimate is a lower bound on
        device time; the slack covers host work and real-world jitter
        while keeping the deadline proportional to the workload instead
        of one global constant."""
        step_s = max(min_step_s, float(est_step_us) * 1e-6 * slack)
        budget = cls(step_s=step_s, slack=slack, **kwargs)
        budget.step_source = 'costmodel'
        return budget

    def note_measured(self, times_s, min_samples=16, quantile=0.95,
                      min_step_s=1.0):
        """Refresh the step budget from MEASURED per-step wall times
        (the ROADMAP item-3 carry-over: budgets from rolling per-step
        profiles, not the analytic estimate).

        ``times_s`` is a window of recent host-side step durations in
        seconds.  The new budget is the window's ``quantile`` x
        ``slack`` (the same slack posture the cost-model derivation
        uses), floored at ``min_step_s``.  Only non-explicit budgets
        adapt: an operator's armed ``step=`` deadline is a contract,
        while the cost-model/default numbers are estimates the
        measured profile strictly improves on.  Returns the new step_s,
        or None when nothing changed (explicit budget, or too few
        samples)."""
        if self.step_source == 'explicit':
            return None
        ts = sorted(float(t) for t in times_s if t is not None)
        if len(ts) < int(min_samples):
            return None
        est = ts[min(len(ts) - 1, int(len(ts) * float(quantile)))]
        new = max(float(min_step_s), est * self.slack)
        self.step_s = new
        self.step_source = 'measured'
        return new

    def reset_measured(self, est_step_us=None, min_step_s=5.0):
        """Forget a MEASURED step budget after a plan swap: the new
        plan's steps share nothing with the degraded plan's p95, so
        the rolling profile must re-learn from scratch.  The budget
        drops back one rung on the adaptation ladder — to the new
        plan's cost-model estimate when one is given, else the global
        default.  Explicit budgets are a contract and never reset.
        Returns the new step_s (None = default)."""
        if self.step_source == 'explicit':
            return None
        if est_step_us:
            self.step_s = max(float(min_step_s),
                              float(est_step_us) * 1e-6 * self.slack)
            self.step_source = 'costmodel'
        else:
            self.step_s = None
            self.step_source = 'default'
        return self.step_s

    @classmethod
    def from_env(cls, text):
        """Parse the PADDLE_TPU_WATCHDOG value: '1'/'on' -> defaults;
        'step=30,collective=5,slack=8' -> explicit numbers."""
        text = (text or '').strip()
        if text.lower() in ('', '0', 'off', 'false'):
            return None
        if text.lower() in ('1', 'on', 'true'):
            return cls()
        kwargs = {}
        keymap = {'step': 'step_s', 'collective': 'collective_s',
                  'slack': 'slack', 'first': 'first_step_s',
                  'grace': 'grace_s'}
        for part in text.split(','):
            if '=' not in part:
                continue
            k, v = part.split('=', 1)
            k = keymap.get(k.strip(), None)
            if k is None:
                continue
            try:
                kwargs[k] = float(v)
            except ValueError:
                pass
        return cls(**kwargs)

    def to_dict(self):
        return {'step_s': self.step_s, 'collective_s': self.collective_s,
                'slack': self.slack, 'first_step_s': self.first_step_s}


def resolve_watchdog(arg):
    """The shared opt-in posture: explicit False -> None (off even if
    the env says on); True -> Budget(); Budget/dict pass through; None
    -> the PADDLE_TPU_WATCHDOG env decides.  Returns a Budget or
    None."""
    if arg is False:
        return None
    if arg is None:
        return Budget.from_env(os.environ.get(WATCHDOG_ENV))
    if arg is True:
        return Budget()
    if isinstance(arg, Budget):
        return arg
    if isinstance(arg, dict):
        return Budget(**arg)
    raise TypeError(f'watchdog= expects bool/dict/Budget, got {arg!r}')


# -- collective-deadline scope (retry() clamps to it) -------------------------

_budget_local = threading.local()


@contextlib.contextmanager
def collective_budget(seconds):
    """Arm a thread-local deadline for the enclosed cluster wait.  The
    host transport wraps its exchanges in this; retry(deadline=) and
    nested transport calls clamp to the REMAINING budget, so no layer
    of retrying can outlive the collective's allowance."""
    prev = getattr(_budget_local, 'deadline', None)
    mine = time.monotonic() + float(seconds)
    _budget_local.deadline = mine if prev is None else min(prev, mine)
    try:
        yield
    finally:
        _budget_local.deadline = prev


def remaining_budget():
    """Seconds left in the innermost armed collective budget, or None
    when no budget is armed.  Never negative."""
    deadline = getattr(_budget_local, 'deadline', None)
    if deadline is None:
        return None
    return max(0.0, deadline - time.monotonic())


# the per-collective allowance of the currently-started Watchdog
# (Budget.collective_s), process-global: the host transport clamps
# every exchange's wait to it, which is what makes
# PADDLE_TPU_WATCHDOG=collective=5 actually bound collectives instead
# of being parsed-and-ignored configuration
_default_collective_s = None


def default_collective_s():
    """The started Watchdog's per-collective budget in seconds, or
    None when no watchdog (or none with collective_s) is running."""
    return _default_collective_s


class Watchdog:
    """Supervise one step loop (and, with a KV client, the cluster's
    heartbeat quorum).  Use as a context manager or start()/stop().

    The step path stays sync-free: ``step_started``/``step_finished``
    are plain attribute writes.  All detection runs on the daemon
    thread at ``poll`` cadence.

    Escalation on a blown step deadline (or lost quorum):
      1. ``timeout`` (or ``quorum_lost``) telemetry event, with rank
         and elapsed/budget attribution;
      2. flight-recorder dump to ``flight_dir`` (post-mortemable);
      3. cluster abort flag via the transport (peers stop waiting);
      4. ``on_escalate(info)`` — the default exits the process with
         WATCHDOG_EXIT_CODE after ``budget.grace_s`` (a cooperative
         exit may beat it when the main thread was stuck in a host
         collective and already raised CoordinatedAbort).  Tests pass
         their own callback.
    """

    def __init__(self, budget=None, name='train', rank=None, world=None,
                 transport=None, kv=None, namespace='ptpu',
                 heartbeat_interval=0.5, peer_stale_s=None,
                 on_escalate=None, flight_dir=None, poll=0.05):
        from ..distributed.collective import HostCollectives
        self.budget = budget or Budget()
        self.name = name
        self.transport = transport
        if self.transport is None and kv is not None:
            self.transport = HostCollectives(client=kv, rank=rank,
                                             world=world,
                                             namespace=namespace)
        self.rank = (self.transport.rank if self.transport is not None
                     else (0 if rank is None else int(rank)))
        self.world = (self.transport.world
                      if self.transport is not None
                      else (1 if world is None else int(world)))
        self.heartbeat_interval = float(heartbeat_interval)
        # a peer is a straggler when its heartbeat is older than the
        # step budget; the quorum is lost when a majority of ranks is
        self.peer_stale_s = (float(peer_stale_s)
                             if peer_stale_s is not None
                             else self.budget.effective_step_s())
        self.on_escalate = on_escalate
        self.flight_dir = flight_dir
        self.poll = float(poll)
        self._thread = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._step_no = None
        self._step_deadline = None
        self._step_started_at = None
        self._straggler_noted = False
        self._escalated = False
        self._peer_flagged = set()
        self.events = []        # local record for tests/reports

    # -- step-loop notifications (sync-free) ---------------------------------

    def step_started(self, step_no, budget_s=None, first=False):
        if budget_s is None:
            budget_s = (self.budget.effective_first_step_s() if first
                        else self.budget.effective_step_s())
        now = time.monotonic()
        with self._lock:
            self._step_no = step_no
            self._step_started_at = now
            self._step_deadline = now + budget_s
            self._straggler_noted = False

    def step_finished(self, step_no=None):
        with self._lock:
            self._step_deadline = None
            self._step_started_at = None

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return self
        global _default_collective_s
        if self.budget.collective_s is not None:
            self._prev_collective_s = _default_collective_s
            _default_collective_s = self.budget.collective_s
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name=f'watchdog-{self.name}',
            daemon=True)
        self._thread.start()
        return self

    def stop(self):
        global _default_collective_s
        if hasattr(self, '_prev_collective_s'):
            _default_collective_s = self._prev_collective_s
            del self._prev_collective_s
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- detection loop ------------------------------------------------------

    def _loop(self):
        last_hb = 0.0
        while not self._stop.wait(self.poll):
            now = time.monotonic()
            if (self.transport is not None
                    and now - last_hb >= self.heartbeat_interval):
                self._publish_heartbeat()
                last_hb = now
                self._check_quorum()
            self._check_step(now)

    def _check_step(self, now):
        with self._lock:
            deadline = self._step_deadline
            started = self._step_started_at
            step_no = self._step_no
            straggler_noted = self._straggler_noted
        if deadline is None or self._escalated:
            return
        elapsed = now - started
        budget = deadline - started
        if not straggler_noted and \
                elapsed > budget * self.budget.straggler_frac:
            with self._lock:
                self._straggler_noted = True
            self._emit('straggler', step=step_no, rank=self.rank,
                       elapsed_s=round(elapsed, 3),
                       budget_s=round(budget, 3))
            self._probe_ledger('straggler')
        if now > deadline:
            self._escalate('timeout', step=step_no,
                           elapsed_s=round(elapsed, 3),
                           budget_s=round(budget, 3))

    def _publish_heartbeat(self):
        tr = self.transport
        try:
            # rank/step/budget ride along so the cluster aggregator's
            # heartbeat join can show WHAT deadline a silent rank was
            # under, not just that it went silent
            doc = json.dumps({'ts': time.time(), 'step': self._step_no,
                              'rank': self.rank,
                              'budget_s': round(
                                  self.budget.effective_step_s(), 3),
                              'budget_source': getattr(
                                  self.budget, 'step_source', None)})
            tr.client.key_value_set_bytes(
                f'{tr.namespace}/hb/r{self.rank}', doc.encode('utf-8'))
        except Exception:
            pass
        try:
            # republish the collective ledger ring at heartbeat
            # cadence: trainer-loop entries (shard_map sync sites)
            # reach peers for cross-rank diffing even when no host
            # collective runs to piggyback on
            from ..distributed.collective import (
                ledger_enabled, get_ledger, LEDGER_KEY)
            if ledger_enabled():
                tr.post_stats(get_ledger(self.rank).frame(),
                              key=LEDGER_KEY)
        except Exception:
            pass

    def _peer_heartbeats(self):
        """{rank: age_s} for every peer with a readable heartbeat —
        via the transport's client-agnostic try_get, so quorum
        detection works on the jax coordination-service client too,
        not only the FileKVStore."""
        tr = self.transport
        if tr is None:
            return {}
        out = {}
        now = time.time()
        for r in range(self.world):
            if r == self.rank:
                continue
            raw = tr.try_get(f'{tr.namespace}/hb/r{r}')
            if raw is None:
                continue
            try:
                out[r] = now - json.loads(raw.decode('utf-8'))['ts']
            except (ValueError, KeyError, UnicodeDecodeError):
                continue
        return out

    def _check_quorum(self):
        if self.world <= 1 or self._escalated:
            return
        ages = self._peer_heartbeats()
        stale = sorted(r for r, age in ages.items()
                       if age > self.peer_stale_s)
        for r in stale:
            if r not in self._peer_flagged:
                self._peer_flagged.add(r)
                self._emit('straggler', peer=r, rank=self.rank,
                           heartbeat_age_s=round(ages[r], 3),
                           stale_after_s=self.peer_stale_s)
                self._probe_ledger('straggler')
        self._peer_flagged -= {r for r in list(self._peer_flagged)
                               if r in ages and
                               ages[r] <= self.peer_stale_s}
        # live = self + peers with fresh (or not-yet-published, i.e.
        # still starting) heartbeats; quorum lost when live ranks are
        # a STRICT minority (live < world/2) — at exactly half (one
        # stale peer of two) the peer's own watchdog/elastic restart
        # handles it, and escalating here too would bill the hang
        # twice against the restart budget
        live = 1 + sum(1 for r, age in ages.items()
                       if age <= self.peer_stale_s)
        unknown = self.world - 1 - len(ages)
        if (live + unknown) * 2 < self.world and self.world > 1:
            self._escalate('quorum_lost', live=live, stale=stale,
                           world=self.world)

    def _probe_ledger(self, trigger):
        """Diff the collective flight-recorder rings on a straggler /
        escalation edge (rank 0 only — one attributed
        ``collective_mismatch`` per incident, not one per rank).
        Never raises; must never kill the watchdog thread."""
        if self.rank != 0 or self.transport is None:
            return None
        try:
            from ..distributed.collective import probe_mismatch
            return probe_mismatch(self.transport, trigger=trigger)
        except Exception:
            return None

    # -- escalation ----------------------------------------------------------

    def _emit(self, kind, **data):
        self.events.append(dict(kind=kind, **data))
        try:
            from .. import telemetry
            telemetry.event(kind, name=self.name, **data)
            telemetry.add(f'watchdog.{kind}')
        except Exception:
            pass

    def _escalate(self, kind, **data):
        if self._escalated:
            return
        self._escalated = True
        info = dict(kind=kind, rank=self.rank, name=self.name, **data)
        # attribute BEFORE the generic escalation event: a ledger
        # divergence turns "rank N hung" into "rank N issued a
        # different collective at seq S (file.py:line)"
        self._probe_ledger(kind)
        self._emit(kind, rank=self.rank, **data)
        # durable evidence BEFORE the abort: this process may be about
        # to _exit, and the flight ring holds the straggler/timeout
        # trail that explains the restart
        try:
            from .. import telemetry
            d = self.flight_dir or telemetry.flight_dir()
            if d:
                path = os.path.join(
                    d, f'flightrec-watchdog-r{self.rank}-'
                       f'{self._step_no}.json')
                telemetry.dump_flight(path)
                info['flight'] = path
        except Exception:
            pass
        if self.transport is not None:
            try:
                self.transport.request_abort(
                    f'watchdog {kind} on rank {self.rank}')
                self._emit('coordinated_abort', rank=self.rank,
                           reason=kind)
            except Exception:
                pass
        if self.on_escalate is not None:
            try:
                self.on_escalate(info)
            except Exception:
                pass
            return
        self._default_escalate(info)

    def _default_escalate(self, info):
        """Grace, then hard exit.  The grace window lets a main thread
        that was stuck in a HOST collective observe the abort flag and
        exit cooperatively (also WATCHDOG_EXIT_CODE, via the worker's
        abort handler); a thread hung inside XLA or a dead fs gets
        os._exit — the only call guaranteed to free the rank so the
        elastic supervisor can respawn it."""
        time.sleep(self.budget.grace_s)
        os._exit(WATCHDOG_EXIT_CODE)
