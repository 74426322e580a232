"""Trainer supervision: watch, restart, clean up local workers.

Reference analogue:
/root/reference/python/paddle/distributed/fleet/launch_utils.py
(`start_local_trainers`:452 spawns one proc per device and the pod
watch loop polls them; `terminate_local_procs`:308 terminates then
SIGKILLs stragglers) and the elastic restart behaviour of
paddle.distributed.fleet.elastic.

TPU-native: one worker process drives all of a host's chips, so the
supervisor watches ONE child per host (more are supported for API
parity).  A dead or hung worker is restarted up to `max_restarts`
times with `PADDLE_ELASTIC_RESTART_COUNT` exported, and the training
loop resumes from the last auto-checkpoint
(incubate.checkpoint.auto_checkpoint) — together they give the
kill-a-worker-mid-training recovery the reference's pod watcher
provides.  Hang detection is a heartbeat FILE (the worker's
auto-checkpoint saves touch it): a stale mtime beyond
`heartbeat_timeout` kills and restarts the worker, mirroring the
reference watchdog's hung-trainer path.
"""
import os
import signal
import subprocess
import sys
import time

from ..resilience import PREEMPTED_EXIT_CODE, GracefulShutdown

__all__ = ['TrainerProc', 'start_local_trainers',
           'terminate_local_procs', 'watch_local_trainers', 'supervise',
           'request_reshape', 'PREEMPTED_EXIT_CODE',
           'DEADLINE_EXIT_CODE']

# returned by watch_local_trainers when its `deadline` expires before
# the workers finish: the supervised run hung (the timeout(1)
# convention code, so shell drivers read it naturally)
DEADLINE_EXIT_CODE = 124


class TrainerProc:
    """Reference launch_utils.py TrainerProc: one supervised worker."""

    def __init__(self):
        self.proc = None
        self.log_fn = None
        self.rank = None
        self.local_rank = None
        self.cmd = None
        self.env = None
        self.restarts = 0
        self.preemptions = 0
        self.reshapes = 0
        self.spawned_at = 0.0


def start_local_trainers(cmds, log_dir=None, envs=None):
    """Spawn one TrainerProc per command (reference
    launch_utils.py:452).  `cmds`: list of argv lists."""
    procs = []
    for rank, cmd in enumerate(cmds):
        env = dict(os.environ if envs is None else envs)
        env['PADDLE_TRAINER_ID'] = str(rank)
        env['PADDLE_RANK_IN_NODE'] = str(rank)
        # worker and supervisor MUST agree on the preemption exit
        # code, or every clean preemption reads as a crash and burns
        # the restart budget (an explicit `envs` dict would otherwise
        # drop the operator's override)
        env['PADDLE_TPU_PREEMPTED_EXIT_CODE'] = str(PREEMPTED_EXIT_CODE)
        t = TrainerProc()
        t.rank = t.local_rank = rank
        t.cmd = list(cmd)
        t.env = env
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            t.log_fn = open(os.path.join(
                log_dir, f'workerlog.{rank}'), 'ab')
        t.proc = subprocess.Popen(
            cmd, env=env, stdout=t.log_fn or None,
            stderr=subprocess.STDOUT if t.log_fn else None)
        t.spawned_at = time.time()
        procs.append(t)
    return procs


def terminate_local_procs(procs, grace=3.0):
    """Terminate, wait, then SIGKILL stragglers (reference
    launch_utils.py:308 — same escalation, shorter waits)."""
    for p in procs:
        if p.proc is not None and p.proc.poll() is None:
            p.proc.terminate()
        if p.log_fn:
            try:
                p.log_fn.close()
            except Exception:
                pass
            p.log_fn = None
    deadline = time.time() + grace
    while time.time() < deadline:
        if all(p.proc is None or p.proc.poll() is not None
               for p in procs):
            return
        time.sleep(0.05)
    for p in procs:
        if p.proc is not None and p.proc.poll() is None:
            try:
                os.kill(p.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
    for p in procs:
        if p.proc is not None:
            try:
                p.proc.wait(timeout=grace)
            except Exception:
                pass


def _restart(t, log_dir=None, preempted=False, reshape=False,
             extra_env=None):
    """Relaunch a worker.  A clean preemption (exit code
    PREEMPTED_EXIT_CODE after a graceful final checkpoint) bumps the
    preemption counter, NOT the restart counter — the max_restarts
    budget is a *failure* budget, and a fleet that preempts a job ten
    times must not exhaust it.  A supervisor-initiated RESHAPE bumps
    its own counter for the same reason (plus `extra_env`: the new
    mesh/plan riding into the next incarnation)."""
    if reshape:
        t.reshapes += 1
    elif preempted:
        t.preemptions += 1
    else:
        t.restarts += 1
    env = dict(t.env)
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    env['PADDLE_ELASTIC_RESTART_COUNT'] = str(t.restarts)
    env['PADDLE_ELASTIC_PREEMPT_COUNT'] = str(t.preemptions)
    env['PADDLE_ELASTIC_RESHAPE_COUNT'] = str(t.reshapes)
    env['PADDLE_TPU_PREEMPTED_EXIT_CODE'] = str(PREEMPTED_EXIT_CODE)
    t.env = env
    if log_dir and t.log_fn is None:
        t.log_fn = open(os.path.join(
            log_dir, f'workerlog.{t.rank}'), 'ab')
    t.proc = subprocess.Popen(
        t.cmd, env=env, stdout=t.log_fn or None,
        stderr=subprocess.STDOUT if t.log_fn else None)
    t.spawned_at = time.time()


def _seed_heartbeat(heartbeat_file):
    with open(heartbeat_file, 'a'):
        os.utime(heartbeat_file, None)


def _heartbeat_age(heartbeat_file):
    """Seconds since the worker last proved liveness.  A MISSING file
    counts as infinitely stale: a worker (or operator) that deleted
    the heartbeat mid-run used to silently disable hang detection —
    exactly when detection matters most.  Any OTHER stat error
    (ESTALE/EIO on a flaky shared fs) counts as fresh: one transient
    hiccup must not SIGKILL a healthy worker and burn a restart."""
    try:
        return time.time() - os.path.getmtime(heartbeat_file)
    except FileNotFoundError:
        return float('inf')
    except OSError:
        return 0.0


def request_reshape(workdir, mesh=None, env=None, reason=None):
    """Queue a coordinated reshape restart for the supervision loop
    watching `workdir` (``watch_local_trainers(reshape_dir=...)``):
    every worker is gracefully terminated and relaunched together
    with `env` merged in (how a new mesh/plan reaches the next
    incarnation) — WITHOUT consuming the max_restarts budget or
    tripping the crash backoff, the same posture as a fleet
    preemption.  Returns the request's seq."""
    from ..resilience.supervisor import write_reshape_request
    return write_reshape_request(workdir, mesh=mesh, env=env,
                                 reason=reason)


def _coordinated_reshape(procs, req, log_dir, on_event,
                         heartbeat_file):
    """Gracefully stop EVERY worker and relaunch them together with
    the request's env merged in — one restart for the whole cluster,
    free of the failure budget."""
    terminate_local_procs(procs, grace=30.0)
    extra = dict(req.get('env') or {})
    mesh = req.get('mesh')
    if mesh:
        extra.setdefault('PADDLE_TPU_RESHAPE_MESH', ','.join(
            f'{a}={s}' for a, s in mesh.items()))
    if heartbeat_file:
        _seed_heartbeat(heartbeat_file)
    for t in procs:
        _restart(t, log_dir, reshape=True, extra_env=extra)
        if on_event:
            on_event('reshape', t)
    try:
        from ..telemetry import event as _tevent
        _tevent('reshape_restore', initiator='supervisor',
                seq=req.get('seq'), mesh=mesh,
                reason=req.get('reason'))
    except Exception:
        pass


def watch_local_trainers(procs, max_restarts=3, poll=0.2,
                         heartbeat_file=None, heartbeat_timeout=None,
                         log_dir=None, on_event=None, shutdown=None,
                         min_preempt_uptime=None, restart_backoff=1.0,
                         restart_backoff_max=30.0, deadline=None,
                         reshape_dir=None):
    """The pod watch loop: poll workers, restart the dead, kill the
    hung (stale or deleted heartbeat), stop everything when one
    fails beyond `max_restarts`.

    Returns 0 when every worker exited cleanly; the failing worker's
    exit code otherwise.  A worker exiting PREEMPTED_EXIT_CODE (its
    GracefulShutdown checkpointed and bowed out) is restarted without
    consuming the max_restarts budget — unless it ran for less than
    `min_preempt_uptime` seconds, which marks a preemption loop (e.g.
    an exit-code env mismatch) and counts as a failure.  When `shutdown` (a
    resilience.GracefulShutdown watching the SUPERVISOR's signals) is
    requested, SIGTERM is forwarded to the workers so they checkpoint,
    and the loop returns PREEMPTED_EXIT_CODE itself — preemption
    propagates cleanly through nested supervision.  `on_event(kind,
    trainer)` (kinds 'exit', 'restart', 'hang', 'preempt', 'backoff',
    'watchdog', 'reshape') observes transitions — tests and progress
    loggers hook it.

    `reshape_dir` arms the supervisor-initiated COORDINATED restart
    path: a ``reshape_request.json`` appearing there (written by
    :func:`request_reshape` / the plan supervisor) with a new seq
    gracefully terminates every worker and relaunches them together
    with the request's env merged in.  Reshapes consume NO
    max_restarts budget and trip NO crash backoff — a planned
    migration is not a failure, exactly like a preemption.

    CRASH restarts (not preemptions) back off exponentially:
    restart k of a worker waits ``min(restart_backoff * 2**(k-1),
    restart_backoff_max)`` seconds before respawning.  A crash-looping
    worker (bad import, poisoned checkpoint) used to burn the whole
    max_restarts budget in milliseconds — with backoff the budget
    spans long enough for a transient cause (NFS blip, node coming
    up) to clear.  Preempted workers still respawn immediately: the
    fleet already imposed that wait.

    `deadline` bounds the WHOLE supervision in wall-clock seconds: a
    cluster that neither completes nor fails within it is torn down
    and the loop returns DEADLINE_EXIT_CODE (124) — chaos soaks use
    this as invariant I7 (complete or die loudly, never hang a
    reservation).  A worker exiting resilience.watchdog's
    WATCHDOG_EXIT_CODE (a self-detected hang) is restarted as a
    normal FAILURE (it consumes the max_restarts budget — a
    deterministic hang must not restart forever) but is surfaced to
    `on_event` as kind 'watchdog' so supervisors and reports can tell
    a hang from a crash.
    """
    from ..resilience.watchdog import WATCHDOG_EXIT_CODE
    watch_deadline = (time.monotonic() + deadline
                      if deadline is not None else None)
    if min_preempt_uptime is None:
        # default 5s, tunable per-deployment: real workers spend far
        # longer than this importing + restoring before any step, but
        # smoke workers (and tests) may legitimately live for less
        min_preempt_uptime = float(os.environ.get(
            'PADDLE_TPU_MIN_PREEMPT_UPTIME', '5'))
    if bool(heartbeat_file) != bool(heartbeat_timeout):
        raise ValueError(
            'heartbeat_file and heartbeat_timeout must be set '
            'together — one without the other silently disables hang '
            'detection')
    if heartbeat_file:
        # seed the heartbeat at supervision start: a worker that
        # hangs BEFORE its first checkpoint touch must still trip
        # the stale-mtime detector
        _seed_heartbeat(heartbeat_file)
    reshape_seq = 0     # act once per NEW request seq
    try:
        while True:
            if reshape_dir is not None:
                from ..resilience.supervisor import \
                    read_reshape_request
                req = read_reshape_request(reshape_dir)
                if req and int(req.get('seq', 0)) > reshape_seq:
                    reshape_seq = int(req['seq'])
                    _coordinated_reshape(procs, req, log_dir,
                                         on_event, heartbeat_file)
                    continue
            if shutdown is not None and shutdown.requested():
                # host preemption reached the supervisor: pass the
                # SIGTERM down (terminate_local_procs starts with
                # terminate() == SIGTERM, so workers run their own
                # graceful checkpoint within the grace window)
                terminate_local_procs(procs, grace=30.0)
                return PREEMPTED_EXIT_CODE
            if watch_deadline is not None and \
                    time.monotonic() > watch_deadline:
                # the I7 backstop: a hung cluster is torn down and
                # reported as a deadline breach, never left running
                terminate_local_procs(procs, grace=3.0)
                return DEADLINE_EXIT_CODE
            alive = False
            for t in procs:
                rc = t.proc.poll()
                if rc is None:
                    alive = True
                    if heartbeat_file and heartbeat_timeout:
                        age = _heartbeat_age(heartbeat_file)
                        if age > heartbeat_timeout:
                            if on_event:
                                on_event('hang', t)
                            t.proc.kill()
                            t.proc.wait()
                            rc = t.proc.returncode
                        else:
                            continue
                    else:
                        continue
                if rc == 0:
                    continue
                preempted = rc == PREEMPTED_EXIT_CODE
                if preempted and \
                        time.time() - t.spawned_at < min_preempt_uptime:
                    # a worker that claims preemption within seconds
                    # of spawning is looping (env mismatch on the
                    # exit code, shutdown tripped at startup) — count
                    # it against the FAILURE budget or an unbounded
                    # free-restart storm respawns forever
                    preempted = False
                # dead worker: restart or give up
                if on_event:
                    on_event('preempt' if preempted
                             else 'watchdog' if rc == WATCHDOG_EXIT_CODE
                             else 'exit', t)
                if not preempted and t.restarts >= max_restarts:
                    terminate_local_procs(
                        [p for p in procs if p is not t])
                    return rc if rc is not None else 1
                if not preempted and restart_backoff > 0:
                    delay = min(restart_backoff * (2 ** t.restarts),
                                restart_backoff_max)
                    if on_event:
                        on_event('backoff', t)
                    try:
                        from ..telemetry import event as _tevent
                        _tevent('restart_backoff', rank=t.rank,
                                restarts=t.restarts,
                                delay_s=round(delay, 3))
                    except Exception:
                        pass
                    # chunked: a SIGTERM (fleet preemption) arriving
                    # mid-backoff must still reach the OTHER workers
                    # within the kill-grace window, not wait out a
                    # 30s sleep in the shared supervision loop
                    deadline = time.monotonic() + delay
                    while time.monotonic() < deadline:
                        if shutdown is not None and \
                                shutdown.requested():
                            terminate_local_procs(procs, grace=30.0)
                            return PREEMPTED_EXIT_CODE
                        time.sleep(min(poll, max(
                            0.0, deadline - time.monotonic())))
                if heartbeat_file:
                    # a fresh heartbeat marks the NEW incarnation live
                    # (and re-seeds a deleted file so detection stays
                    # armed)
                    _seed_heartbeat(heartbeat_file)
                _restart(t, log_dir, preempted=preempted)
                if on_event:
                    on_event('restart', t)
                alive = True
            if not alive:
                return 0
            time.sleep(poll)
    except KeyboardInterrupt:
        terminate_local_procs(procs)
        raise


def supervise(cmd, max_restarts=3, log_dir=None, heartbeat_file=None,
              heartbeat_timeout=None, on_event=None,
              restart_backoff=1.0, restart_backoff_max=30.0):
    """Run ONE worker command under supervision (the per-host elastic
    entry the launcher's --elastic flag uses).  The supervisor itself
    handles SIGTERM gracefully: forward to the worker, let it
    checkpoint, exit PREEMPTED_EXIT_CODE."""
    gs = GracefulShutdown(signals=(signal.SIGTERM,)).install()
    procs = start_local_trainers([cmd], log_dir=log_dir)
    try:
        return watch_local_trainers(
            procs, max_restarts=max_restarts, log_dir=log_dir,
            heartbeat_file=heartbeat_file,
            heartbeat_timeout=heartbeat_timeout, on_event=on_event,
            shutdown=gs, restart_backoff=restart_backoff,
            restart_backoff_max=restart_backoff_max)
    finally:
        gs.uninstall()


