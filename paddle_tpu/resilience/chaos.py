"""paddle_tpu.resilience.chaos — deterministic, seeded fault injection.

The resilience runtime (verified commits, two-phase cross-host
finalize, preemption handling, NaN rollback) makes promises it could
not previously PROVE: nothing in the repo injected the faults those
paths exist for.  This module is that proof harness.

A :class:`FaultPlan` is a declarative, *seeded* list of faults:

    plan = FaultPlan(seed=7, faults=[
        Fault('io_error', path='_PADDLE_COMMIT', prob=0.5,
              errno_name='EIO'),
        Fault('torn_write', at_step=3),
        Fault('sigkill', at_step=5),
        Fault('nan_grads', at_step=4),
    ])

and a :class:`ChaosEngine` applies it through *scoped monkeypatch
seams* on the boundaries real failures hit:

  file seam        ``resilience.manifest.atomic_write`` — EIO/ENOSPC
                   raised mid-commit, slow (sleep-injected) writes,
                   torn writes (the tmp file lands, truncated, WITHOUT
                   the atomic rename — what a dying NFS client leaves)
  ckpt seam        ``distributed.checkpoint._SaveHandle.wait`` — shard
                   truncation / byte corruption / dropped or
                   half-finished commits applied the instant a save
                   barrier completes (exactly when a host dies)
  process seam     ``engine.step(n)`` called from the training loop —
                   SIGTERM (graceful-preemption path) or SIGKILL
                   (crash path) delivered at step N, heartbeat files
                   deleted or back-dated
  grads seam       ``engine.poison(n, *arrays)`` — NaN written into
                   the step-N batch so the compiled step's finiteness
                   reduction (hapi / ParallelTrainer / 1F1B pipeline)
                   sees a genuinely non-finite gradient

Determinism is the load-bearing property: every probabilistic decision
comes from ``random.Random(plan.seed)``, consulted in a fixed seam
order, so the SAME plan replays the SAME injected-fault sequence —
``engine.sequence()`` — twice.  Every injection also lands in
telemetry as a ``fault_injected`` event, which tools/run_report.py
merges into the resilience timeline next to the commit-barrier spans
and rollbacks it provoked.

:func:`check_invariants` is the assertion side: given a checkpoint
directory (and optionally the run's telemetry events) it verifies the
resilience invariant set — restore() can only ever yield a committed,
verifiable step; committed steps are monotonic; preemptions exited
PREEMPTED_EXIT_CODE; restarts stayed within budget.  tools/chaos_run.py
drives a training script under a plan and gates on it;
``tools/soak_run.py --smoke`` runs one short 2-process plan
(tests/test_chaos_cluster.py::TestChaosClusterE2E).
"""
import contextlib
import errno as _errno
import json
import os
import random
import signal
import time

__all__ = ['FAULT_KINDS', 'COLLECTIVE_FAULT_KINDS',
           'SERVING_FAULT_KINDS', 'Fault', 'FaultPlan', 'ChaosEngine',
           'ChaosCallback', 'ChaosCluster', 'ServingFaultInjector',
           'check_invariants', 'plan_from_env', 'load_run_events',
           'PLAN_ENV']

PLAN_ENV = 'PADDLE_TPU_CHAOS_PLAN'

# faults that land on the host-collective wire (distributed.collective
# HostCollectives) — the seam class added for the multi-process chaos
# topology, and the one that must exist BEFORE quantized (EQuARX)
# collectives change what travels on it
COLLECTIVE_FAULT_KINDS = (
    'collective_delay',    # sleep delay_s before posting the payload
    'collective_hang',     # go silent: never post; peers time out and
                           # the abort flag (or delay_s) releases us
    'collective_drop',     # participant drops out: raise mid-collective
    'collective_corrupt',  # flip a payload byte AFTER the crc header
                           # is computed — receivers must detect it
)

# faults that land on the serving fleet (serving/router.py front
# door): injected by the drill driver through ServingFaultInjector's
# two seams, NOT by ChaosEngine's file/step/collective hooks — a
# serving drill has no training step to key on, so these fire on
# stream progress (`after_tokens`) instead of `at_step`.  Opt-in via
# plangen.OPTIN_KINDS, same draw-stream-stability reasoning as
# collective_skip.
SERVING_FAULT_KINDS = (
    'replica_kill',       # SIGKILL a fleet replica once a targeted
                          # stream has emitted after_tokens tokens —
                          # the router must land every in-flight rid
                          # in a terminal state: retried bit-exact on
                          # a survivor, or failed TYPED (never lost)
    'replica_hang',       # SIGSTOP a replica: its streams stall past
                          # the router's read timeout; looks like a
                          # dead peer that still holds the port, so
                          # detection cannot rely on process exit
    'client_disconnect',  # drop the CLIENT connection mid-stream
                          # after after_tokens tokens — the frontend
                          # must evict the rid and roll its tokens
                          # back (PR-12 preemption accounting)
    'slow_client',        # client stops reading between events for
                          # delay_s — backpressure must not hang the
                          # engine thread or starve other streams
)

FAULT_KINDS = (
    'io_error',          # raise OSError(errno) from matching file writes
    'slow_io',           # sleep delay_s inside matching file writes
    'torn_write',        # leave a truncated tmp file, skip the rename
    'drop_commit',       # save barrier passes, manifest never written
    'corrupt_shard',     # flip bytes in the largest committed payload
    'truncate_shard',    # truncate the largest committed payload
    'sigterm',           # graceful preemption at step N
    'sigkill',           # hard crash at step N
    'delete_heartbeat',  # remove the heartbeat file at step N
    'stale_heartbeat',   # back-date the heartbeat mtime at step N
    'nan_grads',         # poison the step-N batch with NaN
    'slow_rank',         # throttle this rank's step N by delay_s (the
                         # straggler the watchdog must attribute)
    'drift',             # emit a synthetic drift_detected at step N
                         # (op + us_ratio): the sustained sensor edge
                         # the plan supervisor must actuate on exactly
                         # once — chaos-grade drift without waiting
                         # for a real profiled collective to degrade
    'collective_skip',   # rank silently SKIPS a matching collective
                         # (no post, no ledger entry) and proceeds —
                         # the SPMD-contract violation the collective
                         # flight recorder must attribute to its call
                         # site.  Deliberately NOT in
                         # COLLECTIVE_FAULT_KINDS: growing that tuple
                         # would shift plangen's seeded draw stream
                         # and break golden-pinned plans (opt-in via
                         # plangen.OPTIN_KINDS, the 'drift' precedent)
) + COLLECTIVE_FAULT_KINDS + SERVING_FAULT_KINDS


class Fault:
    """One declarative fault.

    kind        one of FAULT_KINDS.
    at_step     fire exactly at this training step (process/grads/
                collective seams), or at the save of this step (ckpt
                seam).
    prob        fire probabilistically per opportunity (file seam);
                drawn from the plan's seeded RNG.
    count       max number of injections (default 1 for at_step
                faults, unbounded for prob faults).
    path        substring filter on the file path (file/ckpt seams).
    errno_name  'EIO' | 'ENOSPC' | ... for io_error.
    delay_s     sleep for slow_io / collective_delay / slow_rank, and
                the hang duration cap for collective_hang.
    rank        only fire on this cluster rank (None = any rank) —
                multi-process plans slice per rank; see
                FaultPlan.slice_for_rank.
    op          substring filter on the collective op/tag (collective
                seams; e.g. 'allreduce' or 'step7'), and the drifted
                collective kind for ``drift`` faults (default
                'all-reduce').
    us_ratio    observed/predicted ratio a ``drift`` fault reports
                (default 8.0 — far outside the monitor's 4x band).
    after_tokens  serving seams (SERVING_FAULT_KINDS): fire once the
                targeted stream has emitted this many tokens — the
                serving analogue of at_step (a drill has no training
                step; stream progress is its clock).  `rank` selects
                the replica index for replica_* kinds; `path`
                substring-filters the rid.
    """

    def __init__(self, kind, at_step=None, prob=None, count=None,
                 path=None, errno_name='EIO', delay_s=0.05,
                 rank=None, op=None, us_ratio=None,
                 after_tokens=None):
        if kind not in FAULT_KINDS:
            raise ValueError(f'unknown fault kind {kind!r}; '
                             f'one of {FAULT_KINDS}')
        self.kind = kind
        self.at_step = at_step
        self.prob = prob
        self.count = count if count is not None else \
            (1 if at_step is not None else None)
        self.path = path
        self.errno_name = errno_name
        self.delay_s = delay_s
        self.rank = rank
        self.op = op
        self.us_ratio = us_ratio
        self.after_tokens = None if after_tokens is None \
            else int(after_tokens)
        self.fired = 0

    _FIELDS = ('kind', 'at_step', 'prob', 'count', 'path',
               'errno_name', 'delay_s', 'rank', 'op', 'us_ratio',
               'after_tokens')

    def to_dict(self):
        d = {k: getattr(self, k) for k in self._FIELDS}
        # us_ratio / after_tokens joined the schema after plans were
        # golden-pinned: omit them when unset so every pre-existing
        # plan's canonical JSON (and fingerprint) stays byte-identical
        for late in ('us_ratio', 'after_tokens'):
            if d[late] is None:
                del d[late]
        return d

    @classmethod
    def from_dict(cls, d):
        return cls(**{k: v for k, v in d.items() if k in cls._FIELDS})

    def _exhausted(self):
        return self.count is not None and self.fired >= self.count

    def __repr__(self):
        bits = [self.kind]
        if self.at_step is not None:
            bits.append(f'at_step={self.at_step}')
        if self.prob is not None:
            bits.append(f'prob={self.prob}')
        if self.rank is not None:
            bits.append(f'rank={self.rank}')
        if self.op is not None:
            bits.append(f'op={self.op!r}')
        return f'Fault({", ".join(bits)})'


class FaultPlan:
    """A seeded, declarative set of faults — JSON-serializable so the
    chaos_run driver can ship it to a worker subprocess through one
    env var and a replayed run sees the identical plan."""

    def __init__(self, seed=0, faults=(), name=None):
        self.seed = int(seed)
        self.faults = [f if isinstance(f, Fault) else Fault.from_dict(f)
                       for f in faults]
        self.name = name

    def to_json(self):
        return json.dumps({'seed': self.seed, 'name': self.name,
                           'faults': [f.to_dict() for f in self.faults]},
                          sort_keys=True)

    @classmethod
    def from_json(cls, text):
        d = json.loads(text)
        return cls(seed=d.get('seed', 0), faults=d.get('faults', ()),
                   name=d.get('name'))

    def slice_for_rank(self, rank):
        """This rank's share of a cluster plan: faults addressed to
        `rank` plus the unaddressed ones.  The SEED is unchanged —
        same cluster seed => every rank replays its identical injected
        sequence, and the union over ranks is the plan's sequence."""
        rank = int(rank)
        faults = [Fault.from_dict(f.to_dict()) for f in self.faults
                  if f.rank is None or int(f.rank) == rank]
        return FaultPlan(seed=self.seed, faults=faults,
                         name=f'{self.name or "plan"}@r{rank}')

    def mark_fired(self, events, rank=None):
        """Replay the fault ledger into this plan: count the
        ``fault_injected`` records a PREVIOUS incarnation already
        injected (telemetry JSONL + flight dumps survive the process)
        and advance each bounded fault's ``fired`` counter, so a
        restarted worker re-reading the same plan does not re-kill /
        re-hang itself at the same step forever — while faults it has
        NOT yet reached still fire.  Returns the number of ledger
        entries applied."""
        applied = 0
        for f in self.faults:
            if f.count is None:
                continue        # unbounded prob faults may refire
            n = 0
            for e in events:
                if e.get('kind') != 'fault_injected':
                    continue
                if e.get('fault') != f.kind:
                    continue
                if rank is not None and e.get('rank', 0) != rank:
                    continue
                if f.at_step is not None \
                        and e.get('step') != f.at_step:
                    continue
                if f.op is not None and f.op not in str(
                        e.get('op') or e.get('tag') or ''):
                    continue
                n += 1
            if n:
                f.fired = min(f.count, f.fired + n)
                applied += n
        return applied


def plan_from_env(env=PLAN_ENV):
    """The FaultPlan shipped via the environment, or None.  Workers
    call this at startup so ANY training script becomes chaos-runnable
    without code changes beyond engine.step()/poison() hooks."""
    text = os.environ.get(env)
    return FaultPlan.from_json(text) if text else None


class ServingFaultInjector:
    """Interprets a plan's SERVING_FAULT_KINDS at the fleet drill's
    two seams — the serving counterpart of ChaosEngine (which patches
    file/step/collective seams a serving drill never crosses).

    The drill driver (tests/test_engine_frontdoor.py) calls:

    * :meth:`fleet_faults` from its on_token tap: replica-side kinds
      (replica_kill / replica_hang) due at this stream offset — the
      driver applies them with ``ReplicaHandle.kill(SIGKILL|SIGSTOP)``;
    * :meth:`client_faults` from the client read loop:
      client_disconnect (close the socket now) and slow_client (sleep
      ``delay_s`` before the next read).

    Faults stay declarative and seeded exactly like every other kind:
    same plan JSON => same injected sequence, and each firing is
    recorded so :func:`check_invariants`-style audits can line the
    ledger up against what was actually injected.
    """

    def __init__(self, plan, telemetry=None):
        self.plan = plan
        self.faults = [f for f in plan.faults
                       if f.kind in SERVING_FAULT_KINDS]
        self.telemetry = telemetry
        self.injected = []      # [{'fault', 'rid', 'emitted'}, ...]

    def _due(self, kinds, rid, emitted, replica_index=None):
        out = []
        for f in self.faults:
            if f.kind not in kinds or f._exhausted():
                continue
            if f.path is not None and f.path not in str(rid):
                continue
            if f.after_tokens is not None and emitted < f.after_tokens:
                continue
            if f.rank is not None and replica_index is not None \
                    and int(f.rank) != int(replica_index):
                continue
            f.fired += 1
            rec = {'fault': f.kind, 'rid': rid, 'emitted': emitted}
            self.injected.append(rec)
            if self.telemetry is not None:
                self.telemetry.event('fault_injected', fault=f.kind,
                                     rid=str(rid), emitted=emitted)
            out.append(f)
        return out

    def fleet_faults(self, rid, emitted, replica_index=None):
        """replica_kill / replica_hang due now for stream `rid` at
        global offset `emitted` (replica_index = position of the
        serving replica in the fleet's active list, matched against
        the fault's `rank`)."""
        return self._due(('replica_kill', 'replica_hang'), rid,
                         emitted, replica_index)

    def client_faults(self, rid, emitted):
        """client_disconnect / slow_client due now on `rid`'s client
        connection."""
        return self._due(('client_disconnect', 'slow_client'), rid,
                         emitted)


class ChaosEngine:
    """Applies one FaultPlan through scoped monkeypatch seams.

    Use as a context manager (``with ChaosEngine(plan) as eng:``) or
    via activate()/deactivate().  All patches are process-local and
    fully undone on exit — the `chaos` pytest fixture guarantees
    deactivation even on test failure.
    """

    def __init__(self, plan, heartbeat_file=None, rank=None):
        self.plan = plan if isinstance(plan, FaultPlan) else \
            FaultPlan(**plan) if isinstance(plan, dict) else plan
        self.rng = random.Random(self.plan.seed)
        self.heartbeat_file = heartbeat_file
        self.rank = (int(rank) if rank is not None else
                     int(os.environ.get('PADDLE_TRAINER_ID', 0) or 0))
        self.injected = []          # deterministic injection log
        self._saved = []            # (obj, attr, original) undo stack
        self._active = False
        self._current_step = None   # set by step(); collective faults
                                    # with at_step match against it

    # -- bookkeeping ---------------------------------------------------------

    def record(self, fault, **info):
        """One injection: appended to the deterministic sequence and
        emitted as a ``fault_injected`` telemetry event.  Every entry
        carries a rank (seam-provided, else the engine's own) so
        in-memory consumers and flight-ring copies stay attributable
        without relying on the JSONL writer's per-process tag."""
        fault.fired += 1
        entry = dict(fault=fault.kind, seq=len(self.injected), **info)
        entry.setdefault('rank', self.rank)
        self.injected.append(entry)
        try:
            from .. import telemetry
            telemetry.event('fault_injected', seed=self.plan.seed,
                            plan=self.plan.name, **entry)
            telemetry.add('chaos.injected')
        except Exception:       # pragma: no cover - defensive
            pass
        return entry

    def sequence(self):
        """The injected-fault sequence so far — the replayability
        contract: same plan (same seed), same scenario ⇒ identical
        sequence."""
        return list(self.injected)

    def _matching(self, kinds, path=None, step=None, op=None,
                  rank=None):
        """Armed faults of `kinds` matching the path/step/op/rank
        filters, in plan order (deterministic).  `rank` defaults to
        the engine's own rank; the collective seam passes the POSTING
        transport's rank instead (class-level patches see every
        transport in the process — in-process multi-rank tests would
        otherwise misattribute rank-addressed wire faults)."""
        rank = self.rank if rank is None else int(rank)
        out = []
        for f in self.plan.faults:
            if f.kind not in kinds or f._exhausted():
                continue
            if f.rank is not None and int(f.rank) != rank:
                continue
            if path is not None and f.path is not None \
                    and f.path not in str(path):
                continue
            if step is not None and f.at_step is not None \
                    and f.at_step != step:
                continue
            if path is None and f.path is not None:
                continue
            # a drift fault's `op` is PAYLOAD (which collective the
            # synthetic sensor edge reports), not an op-seam address —
            # the step loop that fires it has no op context
            if f.op is not None and f.kind != 'drift' \
                    and (op is None or f.op not in str(op)):
                continue
            out.append(f)
        return out

    def _roll(self, fault):
        """Seeded probability gate.  at_step faults fire
        deterministically; prob faults consult the plan RNG — one draw
        per opportunity, so the decision stream is a pure function of
        the seed and the seam-call order."""
        if fault.prob is None:
            return True
        return self.rng.random() < fault.prob

    # -- seams ---------------------------------------------------------------

    def _patch(self, obj, attr, repl):
        self._saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, repl)

    def activate(self):
        if self._active:
            return self
        from . import manifest as _manifest
        from ..distributed import checkpoint as _ckpt

        orig_write = _manifest.atomic_write

        def chaotic_atomic_write(path, write_fn, mode='w',
                                 prefix='.tmp'):
            for f in self._matching(('io_error',), path=path):
                if self._roll(f):
                    self.record(f, path=str(path),
                                errno=f.errno_name)
                    code = getattr(_errno, f.errno_name, _errno.EIO)
                    raise OSError(code, os.strerror(code), str(path))
            for f in self._matching(('slow_io',), path=path):
                if self._roll(f):
                    self.record(f, path=str(path), delay_s=f.delay_s)
                    time.sleep(f.delay_s)
            for f in self._matching(('torn_write',), path=path):
                if self._roll(f):
                    # what a dying writer leaves on a non-atomic fs:
                    # half the bytes under the REAL name, no fsync, no
                    # rename discipline — the strongest tear the
                    # verify/quarantine path must catch
                    import io
                    buf = io.BytesIO() if 'b' in mode else io.StringIO()
                    write_fn(buf)
                    data = buf.getvalue()
                    half = data[:max(1, len(data) // 2)]
                    with open(path, 'wb' if 'b' in mode else 'w') as fh:
                        fh.write(half)
                    self.record(f, path=str(path),
                                bytes_kept=len(half))
                    return
            return orig_write(path, write_fn, mode=mode, prefix=prefix)

        self._patch(_manifest, 'atomic_write', chaotic_atomic_write)

        orig_wait = _ckpt._SaveHandle.wait
        eng = self

        def chaotic_wait(handle):
            step = getattr(handle, '_step', None)
            for f in eng._matching(('drop_commit',), step=step):
                if eng._roll(f):
                    # the save barrier drains but the process "dies"
                    # before its commit: exactly the SIGKILL-between-
                    # save-and-commit window, minus the actual kill
                    if hasattr(handle._ckptr, 'wait_until_finished'):
                        handle._ckptr.wait_until_finished()
                    handle._ckptr.close()
                    handle._drained = True
                    handle._done = True
                    eng.record(f, step=step)
                    return
            orig_wait(handle)
            for f in eng._matching(('corrupt_shard', 'truncate_shard'),
                                   step=step):
                if eng._roll(f):
                    # handle has no path; the fault carries it
                    target = f.path
                    if target and os.path.isdir(target):
                        victim = eng._damage_dir(target,
                                                 flip=f.kind ==
                                                 'corrupt_shard')
                        eng.record(f, step=step, path=victim)

        self._patch(_ckpt._SaveHandle, 'wait', chaotic_wait)
        self._install_collective_seams()
        self._active = True
        return self

    def _install_collective_seams(self):
        """Patch the host-collective transport's post() (class-level:
        every HostCollectives instance in this process).  The four wire
        faults live here because this is where a real cluster fails:
        a slow NIC (delay), a hung peer (hang), a crashed peer
        (drop), and bit rot on the wire (corrupt) — all BEFORE the
        payload leaves this rank, so the injected byte damage must be
        caught by the receivers' frame checks, whatever the dtype."""
        from ..distributed import collective as _coll

        eng = self
        orig_post = _coll.HostCollectives.post

        def chaotic_post(transport, tag, op, payload):
            label = f'{op}:{tag}'
            step = eng._current_step

            def armed(f):
                # mirror the process seam's explicit recheck: an
                # at_step fault must not fire on collectives that run
                # BEFORE the loop's first engine.step() (startup
                # barriers/broadcasts), when _current_step is None and
                # _matching's step filter is vacuous
                if f.at_step is not None and f.at_step != step:
                    return False
                return eng._roll(f)
            for f in eng._matching(('collective_drop',), step=step,
                                   op=label,
                                   rank=transport.rank):
                if armed(f):
                    eng.record(f, op=op, tag=tag, rank=transport.rank,
                               step=step)
                    raise RuntimeError(
                        f'chaos: injected participant drop in '
                        f'{op}[{tag}] on rank {eng.rank}')
            for f in eng._matching(('collective_hang',), step=step,
                                   op=label,
                                   rank=transport.rank):
                if armed(f):
                    eng.record(f, op=op, tag=tag, rank=transport.rank,
                               step=step, delay_s=f.delay_s)
                    # go silent: peers see a missing participant and
                    # time out; we wake early only for the cluster
                    # abort flag (the coordinated-abort release) or
                    # the hang cap (a straggler that finally arrives)
                    deadline = time.monotonic() + f.delay_s
                    while time.monotonic() < deadline:
                        doc = transport.abort_requested()
                        if doc is not None:
                            from ..distributed.collective import \
                                CoordinatedAbort
                            raise CoordinatedAbort(
                                f'chaos hang in {op}[{tag}] released '
                                f'by abort from rank '
                                f'{doc.get("rank")}')
                        time.sleep(min(0.02, f.delay_s))
            for f in eng._matching(('collective_delay',), step=step,
                                   op=label,
                                   rank=transport.rank):
                if armed(f):
                    eng.record(f, op=op, tag=tag, rank=transport.rank,
                               step=step, delay_s=f.delay_s)
                    time.sleep(f.delay_s)
            for f in eng._matching(('collective_corrupt',), step=step,
                                   op=label,
                                   rank=transport.rank):
                if armed(f):
                    eng.record(f, op=op, tag=tag, rank=transport.rank,
                               step=step)
                    # flip one payload byte AFTER the crc header was
                    # computed: receivers MUST reject the frame
                    b = bytearray(payload)
                    b[-1] ^= 0xFF
                    payload = bytes(b)
            return orig_post(transport, tag, op, payload)

        self._patch(_coll.HostCollectives, 'post', chaotic_post)

        orig_exchange = _coll.HostCollectives._exchange

        def chaotic_exchange(transport, tag, op, arr, timeout_s=None,
                             quant=None):
            # collective_skip intercepts the WHOLE exchange (not just
            # the post): the rank records nothing in its ledger, posts
            # nothing, waits for nobody, and proceeds with its own
            # contribution — the rank-gated skipped collective whose
            # divergence the flight recorder must attribute
            label = f'{op}:{tag}'
            step = eng._current_step
            for f in eng._matching(('collective_skip',), step=step,
                                   op=label, rank=transport.rank):
                if f.at_step is not None and f.at_step != step:
                    continue
                if not eng._roll(f):
                    continue
                eng.record(f, op=op, tag=tag, rank=transport.rank,
                           step=step)
                import numpy as _np
                return {transport.rank: _np.asarray(arr)}
            return orig_exchange(transport, tag, op, arr,
                                 timeout_s=timeout_s, quant=quant)

        self._patch(_coll.HostCollectives, '_exchange',
                    chaotic_exchange)

    def deactivate(self):
        while self._saved:
            obj, attr, orig = self._saved.pop()
            setattr(obj, attr, orig)
        self._active = False

    def __enter__(self):
        return self.activate()

    def __exit__(self, *exc):
        self.deactivate()
        return False

    @staticmethod
    def _damage_dir(directory, flip=True):
        """Largest payload file in `directory`: byte-flip (bit-level
        corruption under an intact size) or truncate (torn write)."""
        from .manifest import MANIFEST_NAME, TWO_PHASE_DIR
        victim, size = None, -1
        for root, dirs, files in os.walk(directory):
            if TWO_PHASE_DIR in dirs:
                dirs.remove(TWO_PHASE_DIR)
            for f in files:
                if f == MANIFEST_NAME:
                    continue
                p = os.path.join(root, f)
                if os.path.getsize(p) > size:
                    victim, size = p, os.path.getsize(p)
        if victim is None:
            return None
        with open(victim, 'r+b') as fh:
            if flip:
                fh.seek(max(0, size // 2))
                b = fh.read(1)
                fh.seek(max(0, size // 2))
                fh.write(bytes([(b[0] ^ 0xFF) if b else 0xFF]))
            else:
                fh.truncate(max(0, size // 2))
        return victim

    # -- process / heartbeat seam -------------------------------------------

    def step(self, step_no):
        """Call once per training step (the chaos_run worker and the
        ChaosCallback do).  Fires process-level faults scheduled for
        this step: SIGTERM (latched by GracefulShutdown → graceful
        preemption), SIGKILL (hard crash), heartbeat tampering,
        slow-rank throttling.  Also advances the step the collective
        seams match ``at_step`` against."""
        self._current_step = step_no
        for f in self._matching(('slow_rank',), step=step_no):
            if f.at_step == step_no and self._roll(f):
                # the deliberate straggler: this rank's step runs, just
                # late — the watchdog's soft threshold must attribute
                # it without killing anything
                self.record(f, step=step_no, rank=self.rank,
                            delay_s=f.delay_s)
                time.sleep(f.delay_s)
        for f in self._matching(('drift',), step=step_no):
            if f.at_step == step_no and self._roll(f):
                # synthetic sensor edge: the SAME drift_detected event
                # telemetry.monitors latches off a real profiled
                # collective, minus the hours of waiting — the plan
                # supervisor must classify, re-plan and actuate on it
                # exactly once
                op = f.op or 'all-reduce'
                ratio = float(f.us_ratio or 8.0)
                self.record(f, step=step_no, op=op, us_ratio=ratio)
                try:
                    from .. import telemetry
                    telemetry.event(
                        'drift_detected', cause='us_ratio', op=op,
                        instr='chaos-injected', us_ratio=ratio,
                        band=4.0, windows=8)
                except Exception:
                    pass
        for f in self._matching(('delete_heartbeat',), step=step_no):
            if f.at_step == step_no and self._roll(f):
                hb = self.heartbeat_file
                self.record(f, step=step_no, path=hb)
                if hb:
                    try:
                        os.remove(hb)
                    except OSError:
                        pass
        for f in self._matching(('stale_heartbeat',), step=step_no):
            if f.at_step == step_no and self._roll(f):
                hb = self.heartbeat_file
                self.record(f, step=step_no, path=hb)
                if hb and os.path.exists(hb):
                    past = time.time() - 10_000
                    os.utime(hb, (past, past))
        for f in self._matching(('sigterm',), step=step_no):
            if f.at_step == step_no and self._roll(f):
                self.record(f, step=step_no, signum=int(signal.SIGTERM))
                os.kill(os.getpid(), signal.SIGTERM)
        for f in self._matching(('sigkill',), step=step_no):
            if f.at_step == step_no and self._roll(f):
                self.record(f, step=step_no, signum=int(signal.SIGKILL))
                # record must be durable first: SIGKILL gives no
                # chance to flush anything afterwards
                try:
                    from .. import telemetry
                    d = telemetry.flight_dir()
                    if d:
                        telemetry.dump_flight(os.path.join(
                            d, f'flightrec-chaos-kill-{step_no}.json'))
                except Exception:
                    pass
                os.kill(os.getpid(), signal.SIGKILL)

    def poison(self, step_no, *arrays):
        """NaN-inject the step-N batch (grads seam): returns the
        arrays, with element [0, ...] of each set to NaN when a
        ``nan_grads`` fault fires for this step.  Works on numpy
        arrays; float arrays only (ids pass through untouched)."""
        import numpy as np
        fired = False
        for f in self._matching(('nan_grads',), step=step_no):
            if f.at_step == step_no and self._roll(f):
                self.record(f, step=step_no)
                fired = True
        if not fired:
            return arrays if len(arrays) != 1 else arrays[0]
        out = []
        for a in arrays:
            a = np.array(a, copy=True)
            if np.issubdtype(a.dtype, np.floating):
                a.reshape(-1)[0] = np.nan
            out.append(a)
        return tuple(out) if len(out) != 1 else out[0]


class ChaosCallback:
    """hapi-style callback adapter: drives ``engine.step`` from
    ``Model.fit``'s batch boundary so a FaultPlan's process-level
    faults apply to hapi training loops too (duck-typed — hapi only
    calls the hooks a callback defines)."""

    def __init__(self, engine):
        self.engine = engine
        self._step = 0

    def set_model(self, model):
        self.model = model

    def on_train_batch_end(self, step, logs=None):
        self._step += 1
        self.engine.step(self._step)


# -- invariant checking --------------------------------------------------------

def check_invariants(ckpt_dir, prefix='step', events=None,
                     max_restarts=None, restarts=None,
                     preempt_codes=(), expect_committed=True,
                     final_rc=None, duration_s=None, deadline_s=None):
    """Verify the resilience invariant set after a chaos run.

    Returns a list of violation strings (empty == all invariants held):

      I1  every COMMITTED step dir verifies (presence+size+digest) —
          restore() can therefore only ever yield a committed step;
      I2  committed steps seen over time are monotonic
          (``checkpoint_commit`` telemetry events, when provided);
      I3  every restore landed on a step that was committed at the
          time (``checkpoint_restore`` step ∈ committed set);
      I4  preemptions exited PREEMPTED_EXIT_CODE (`preempt_codes`:
          exit codes the supervisor attributed to preemption);
      I5  restarts stayed within budget (when both given);
      I6  no step is published (committed) twice after a restart
          unless an intervening restore rolled back BELOW it — a
          restarted worker that re-commits work it never un-did is
          double-publishing state;
      I7  the cluster either completed (rc 0) or exited preempted,
          within the deadline budget — a deadlocked or hung cluster
          (any other rc, or `duration_s` > `deadline_s`) is itself an
          invariant violation, whatever its checkpoints look like.
    """
    from . import manifest as M
    from .shutdown import PREEMPTED_EXIT_CODE
    violations = []
    committed = []
    if os.path.isdir(ckpt_dir):
        for f in sorted(os.listdir(ckpt_dir)):
            tag = f[len(prefix) + 1:]
            if not (f.startswith(prefix + '_') and tag.isdigit()):
                continue
            p = os.path.join(ckpt_dir, f)
            if not M.is_committed(p):
                continue
            committed.append(int(tag))
            ok, errs = M.verify_manifest(p)
            if not ok:
                violations.append(
                    f'I1: committed step {tag} fails verification: '
                    f'{errs[:3]}')
    elif expect_committed:
        violations.append(f'I1: checkpoint dir {ckpt_dir} missing')
    if expect_committed and not committed:
        violations.append('I1: no committed step survived the run')
    if events:
        commits = [e.get('step') for e in events
                   if e.get('kind') == 'checkpoint_commit'
                   and e.get('step') is not None]
        # per-incarnation streams may interleave after a rollback
        # restore — monotonic within each rank's stream order is the
        # invariant (a later commit may legitimately re-commit an
        # EARLIER step only after a restore to it).  Restores are
        # emitted as spans (kind='span', name='checkpoint_restore').
        restores = [e.get('step') for e in events
                    if (e.get('kind') == 'checkpoint_restore'
                        or (e.get('kind') == 'span'
                            and e.get('name') == 'checkpoint_restore'))
                    and e.get('step') is not None]
        lo = None
        restored = set(restores)
        for s in commits:
            if lo is not None and s < lo and s not in restored \
                    and (s + 1) not in restored:
                violations.append(
                    f'I2: commit steps not monotonic: {s} after {lo} '
                    'with no intervening restore')
            lo = s if lo is None else max(lo, s)
        commit_set = set(commits) | set(committed)
        for s in restores:
            if s not in commit_set:
                violations.append(
                    f'I3: restore yielded step {s}, which was never '
                    'committed')
        # I6: a step may be committed AGAIN only after a restore that
        # rolled back below it (the replay then legitimately re-earns
        # it).  Walk the merged stream in order, tracking whether a
        # sufficiently-deep restore separates the two commits.
        commit_or_restore = [
            e for e in events
            if (e.get('kind') == 'checkpoint_commit'
                and e.get('step') is not None)
            or ((e.get('kind') == 'checkpoint_restore'
                 or (e.get('kind') == 'span'
                     and e.get('name') == 'checkpoint_restore'))
                and e.get('step') is not None)]
        seen_commit = {}        # step -> index of its last commit
        for i, e in enumerate(commit_or_restore):
            s = e.get('step')
            if e.get('kind') == 'checkpoint_commit':
                if s in seen_commit:
                    prev = seen_commit[s]
                    rolled_back = any(
                        r.get('kind') != 'checkpoint_commit'
                        and r.get('step') < s
                        for r in commit_or_restore[prev + 1:i])
                    if not rolled_back:
                        violations.append(
                            f'I6: step {s} published twice with no '
                            'intervening restore below it')
                seen_commit[s] = i
    for code in preempt_codes:
        if code != PREEMPTED_EXIT_CODE:
            violations.append(
                f'I4: preemption exited {code}, expected '
                f'{PREEMPTED_EXIT_CODE}')
    if max_restarts is not None and restarts is not None \
            and restarts > max_restarts:
        violations.append(
            f'I5: {restarts} failure restarts exceed the '
            f'max_restarts={max_restarts} budget')
    if final_rc is not None and final_rc not in (
            0, PREEMPTED_EXIT_CODE):
        violations.append(
            f'I7: cluster neither completed nor exited preempted '
            f'(rc={final_rc})')
    if deadline_s is not None and duration_s is not None \
            and duration_s > deadline_s:
        violations.append(
            f'I7: run took {duration_s:.1f}s, past the '
            f'{deadline_s:.1f}s deadline budget')
    return violations


class ChaosCluster:
    """A true multi-process chaos topology: N worker processes under
    elastic supervision, one shared filesystem KV transport, one
    seeded FaultPlan sliced per rank.

    Each worker is a separate interpreter (tools/soak_run.py
    ``--worker`` by default) that: joins the cluster's
    :class:`~paddle_tpu.distributed.collective.FileKVStore` transport
    (restart-proof — the jax coordination service cannot re-admit a
    SIGKILLed task, files can; workers still ``jax.distributed``-
    initialize when `jax_distributed` is set and the plan kills
    nobody), activates its per-rank plan slice (same cluster seed =>
    identical injected sequence every run), trains the deterministic
    workload with a host all-reduce every step, two-phase-commits
    per-rank checkpoint shards, and runs a
    :class:`~paddle_tpu.resilience.watchdog.Watchdog` so a hung
    collective escalates timeout -> flight dump -> coordinated abort
    -> WATCHDOG_EXIT_CODE instead of deadlocking the cluster.

    ``run()`` supervises to completion (bounded by `deadline_s`),
    merges every incarnation's telemetry, and checks invariants I1-I7
    plus cross-rank final-state agreement.  Teardown is guaranteed:
    worker processes are terminated and any coordinator-side seams
    deactivated even when a worker dies mid-plan (the killed-worker
    case the PR-5 reverse-order teardown fix is mirrored for)."""

    def __init__(self, procs=2, plan=None, steps=20, workdir=None,
                 max_restarts=4, save_every=2, collective_timeout_s=30.0,
                 barrier_timeout_s=20.0, watchdog='step=90,grace=2',
                 worker_argv=None, deadline_s=240.0,
                 jax_distributed=False, engine=None, extra_env=None,
                 cluster_stats=False, cluster_stats_interval=0.25,
                 restart_backoff=0.2, restart_backoff_max=2.0,
                 supervisor=None):
        import tempfile
        self.procs = int(procs)
        # crash-restart backoff (seconds, exponential up to the max).
        # The cluster-obs smoke widens it so a SIGKILLed rank stays
        # down long enough for the live view's stale-marking to be
        # observable by a 200ms scraper — with the default snappy
        # respawn the degraded window can close before one scrape.
        self.restart_backoff = float(restart_backoff)
        self.restart_backoff_max = float(restart_backoff_max)
        # cluster_stats: arm the live training observability plane
        # (telemetry.cluster) inside the workers — every rank
        # publishes stats frames over the cluster's own KV transport,
        # rank 0 aggregates and serves /cluster/status.json on an
        # ephemeral 127.0.0.1 port written to
        # <workdir>/cluster_port.json so the supervisor (or a test)
        # can scrape a LIVE view of the chaos run.  The plane must survive every fault the plan
        # injects: a killed rank degrades the view (stale-marked),
        # never crashes it.
        self.cluster_stats = bool(cluster_stats)
        self.cluster_stats_interval = float(cluster_stats_interval)
        # supervisor: arm the self-healing plan supervisor inside the
        # workers (resilience.supervisor posture string/'1') AND the
        # coordinated-reshape watch on this supervision loop — a
        # rank-0 worker's swap request restarts the whole cluster
        # once, free of the max_restarts budget.
        self.supervisor = supervisor
        self.plan = (plan if isinstance(plan, FaultPlan)
                     else FaultPlan(**plan) if isinstance(plan, dict)
                     else plan or FaultPlan(seed=0))
        self.steps = int(steps)
        self.workdir = workdir or tempfile.mkdtemp(prefix='chaos_cluster_')
        self.max_restarts = max_restarts
        self.save_every = save_every
        self.collective_timeout_s = collective_timeout_s
        self.barrier_timeout_s = barrier_timeout_s
        self.watchdog = watchdog
        self.worker_argv = worker_argv
        self.deadline_s = deadline_s
        self.jax_distributed = jax_distributed
        # an optional coordinator-side engine (callers injecting
        # supervisor-level faults); run() owns its teardown
        self.engine = engine
        self.extra_env = dict(extra_env or {})

    def _default_worker(self):
        import sys
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        return [sys.executable,
                os.path.join(repo, 'tools', 'soak_run.py'), '--worker']

    def _worker_env(self):
        import sys
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env.update({
            'JAX_PLATFORMS': 'cpu',
            'PYTHONPATH': repo + os.pathsep + env.get('PYTHONPATH', ''),
            'PADDLE_TPU_KV': 'file:' + os.path.join(self.workdir, 'kv'),
            'PADDLE_TRAINERS_NUM': str(self.procs),
            'PADDLE_TPU_CHAOS_PLAN': self.plan.to_json(),
            'PADDLE_TPU_CHAOS_STEPS': str(self.steps),
            'PADDLE_TPU_CHAOS_DIR': self.workdir,
            'PADDLE_TPU_SOAK_SAVE_EVERY': str(self.save_every),
            'PADDLE_TPU_SOAK_COLLECTIVE_TIMEOUT':
                str(self.collective_timeout_s),
            'PADDLE_TPU_SOAK_BARRIER_TIMEOUT':
                str(self.barrier_timeout_s),
            'PADDLE_TPU_SOAK_JAXDIST':
                '1' if self.jax_distributed else '0',
            'PADDLE_TPU_WATCHDOG': self.watchdog or '0',
            'PADDLE_TPU_MIN_PREEMPT_UPTIME': '0',
        })
        if self.cluster_stats:
            env['PADDLE_TPU_CLUSTER_STATS'] = str(
                self.cluster_stats_interval)
        if self.supervisor:
            env['PADDLE_TPU_SUPERVISOR'] = (
                '1' if self.supervisor is True else str(self.supervisor))
        if self.jax_distributed:
            import socket
            s = socket.socket()
            s.bind(('127.0.0.1', 0))
            port = s.getsockname()[1]
            s.close()
            env['PADDLE_TPU_SOAK_COORD'] = f'127.0.0.1:{port}'
        env.update({k: str(v) for k, v in self.extra_env.items()})
        return env

    def run(self):
        """Supervise one full chaos soak; returns the report dict
        (ok, violations, injected sequence, incarnations, finals)."""
        from ..distributed import elastic
        os.makedirs(os.path.join(self.workdir, 'kv'), exist_ok=True)
        cmd = list(self.worker_argv or self._default_worker())
        t0 = time.time()
        supervisor_events = []
        exit_codes = {'preempt': [], 'exit': [], 'watchdog': []}

        def on_event(kind, t):
            supervisor_events.append((kind, t.rank))
            rc = t.proc.returncode if t.proc else None
            if kind in exit_codes and rc is not None:
                exit_codes[kind].append(rc)

        procs = elastic.start_local_trainers(
            [cmd] * self.procs, envs=self._worker_env(),
            log_dir=os.path.join(self.workdir, 'logs'))
        try:
            rc = elastic.watch_local_trainers(
                procs, max_restarts=self.max_restarts, poll=0.05,
                min_preempt_uptime=0.0, on_event=on_event,
                restart_backoff=self.restart_backoff,
                restart_backoff_max=self.restart_backoff_max,
                deadline=self.deadline_s,
                reshape_dir=self.workdir if self.supervisor else None)
        finally:
            elastic.terminate_local_procs(procs, grace=2.0)
            if self.engine is not None:
                # mirror of the PR-5 reverse-order teardown fix for the
                # collective seam class: a worker SIGKILLed mid-plan
                # must not leave the coordinator's transport patched
                self.engine.deactivate()
        duration = time.time() - t0

        events = load_run_events(self.workdir)
        injected = [e for e in events
                    if e.get('kind') == 'fault_injected']
        restarts = max((p.restarts for p in procs), default=0)
        violations = check_invariants(
            os.path.join(self.workdir, 'ckpt'), events=events,
            max_restarts=self.max_restarts, restarts=restarts,
            preempt_codes=exit_codes['preempt'], final_rc=rc,
            duration_s=duration, deadline_s=self.deadline_s)
        finals = self._load_finals()
        if rc == 0:
            if len(finals) != self.procs:
                violations.append(
                    f'only {sorted(finals)} of {self.procs} ranks '
                    'wrote a final state')
            elif len({json.dumps(v['final_w']) for v in
                      finals.values()}) > 1:
                violations.append(
                    'ranks disagree on the final state — a collective '
                    'fault leaked into the arithmetic')
        return {
            'ok': not violations,
            'violations': violations,
            'plan': json.loads(self.plan.to_json()),
            'procs': self.procs,
            'steps': self.steps,
            'rc': rc,
            'injected': [{k: e.get(k) for k in
                          ('fault', 'step', 'path', 'seq', 'errno',
                           'op', 'tag', 'rank')
                          if e.get(k) is not None} for e in injected],
            'incarnations': {p.rank: 1 + p.restarts + p.preemptions
                             + p.reshapes for p in procs},
            'failure_restarts': {p.rank: p.restarts for p in procs},
            'preemptions': {p.rank: p.preemptions for p in procs},
            'reshapes': {p.rank: p.reshapes for p in procs},
            'preempt_exit_codes': exit_codes['preempt'],
            'watchdog_exit_codes': exit_codes['watchdog'],
            'supervisor_events': supervisor_events,
            'duration_s': round(duration, 2),
            'finals': finals,
            'workdir': self.workdir,
            'events': len(events),
            'cluster_port_file': (self.cluster_port_file
                                  if self.cluster_stats else None),
        }

    @property
    def cluster_port_file(self):
        """Where rank 0's aggregator publishes its bound HTTP port
        (written by the worker once the MetricsServer is up)."""
        return os.path.join(self.workdir, 'cluster_port.json')

    def _load_finals(self):
        out = {}
        for r in range(self.procs):
            p = os.path.join(self.workdir, f'out_r{r}.json')
            try:
                with open(p) as f:
                    out[r] = json.load(f)
            except (OSError, ValueError):
                continue
        return out


def load_run_events(workdir):
    """Every telemetry event of a supervised run under `workdir`:
    streamed JSONL plus the event rings of any flight-recorder dumps
    (a SIGKILLed or watchdog-killed incarnation's last moments only
    survive in its pre-kill dump).  Deduped and wall-clock ordered —
    the input to check_invariants(events=...)."""
    import glob
    events = []
    for f in sorted(glob.glob(os.path.join(
            workdir, '**', 'telemetry-*.jsonl'), recursive=True)):
        with open(f) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue        # torn final line of a dead worker
                if isinstance(rec, dict) and 'kind' in rec:
                    events.append(rec)
    for f in sorted(glob.glob(os.path.join(
            workdir, '**', 'flightrec-*.json'), recursive=True)):
        try:
            with open(f) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        rank = doc.get('rank', 0)
        for rec in doc.get('events', []):
            if isinstance(rec, dict) and 'kind' in rec:
                rec = dict(rec)
                rec.setdefault('rank', rank)
                events.append(rec)
    # an event both streamed and ring-dumped collapses to one, and the
    # merged stream is replayed in wall-clock order
    seen, out = set(), []
    for e in events:
        k = (e.get('ts'), e.get('t'), e.get('kind'), e.get('rank', 0))
        if k in seen:
            continue
        seen.add(k)
        out.append(e)
    out.sort(key=lambda e: e.get('ts') or 0)
    return out
