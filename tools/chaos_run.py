#!/usr/bin/env python
"""chaos_run — run a training workload under a seeded FaultPlan and
assert the resilience invariant set.

The executable half of paddle_tpu.resilience.chaos: a supervisor
(elastic.watch_local_trainers) drives a worker training loop while the
plan injects faults INSIDE it (torn manifests, dropped commits, EIO,
SIGKILL/SIGTERM at step N, NaN grads), then the run's checkpoints and
telemetry are checked against the invariants the resilience runtime
promises:

    I1  restore() only ever yields a committed, verifiable step
    I2  committed steps are monotonic (modulo explicit restores)
    I3  every restore landed on a committed step
    I4  preemptions exited PREEMPTED_EXIT_CODE (117)
    I5  restarts stayed within the failure budget
    +   the finished run's final state equals an uninterrupted run's
        (the workload is a pure function of the step index)

Usage:

    python tools/chaos_run.py                         # default plan
    python tools/chaos_run.py --plan plan.json        # your plan
    python tools/chaos_run.py --plan '{"seed":7,...}' # inline JSON
    python tools/chaos_run.py --smoke --json          # CI gate
    python tools/chaos_run.py --script train.py a b   # your script

With ``--script`` the plan is exported as PADDLE_TPU_CHAOS_PLAN and
the script is supervised as-is — it opts in by calling
``chaos.plan_from_env()`` + ``ChaosEngine.step()`` in its loop (see
the built-in worker at the bottom of this file for the pattern).
Exit code 0 iff every invariant held.
"""
import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

STEPS_ENV = 'PADDLE_TPU_CHAOS_STEPS'
DIR_ENV = 'PADDLE_TPU_CHAOS_DIR'

# the default plan: a hard kill mid-run, one torn manifest write, one
# dropped commit — the three crash shapes the commit protocol exists
# for.  Seeded so two runs inject the identical sequence.
DEFAULT_PLAN = {
    'seed': 7,
    'name': 'smoke',
    'faults': [
        {'kind': 'sigkill', 'at_step': 5},
        {'kind': 'torn_write', 'at_step': None, 'path': 'step_7'},
        {'kind': 'drop_commit', 'at_step': 9},
    ],
}


def _final_w(steps):
    """The workload's exact final state: w_i = 0.9 * w_{i-1} + i over
    float32 — pure in the step index, so ANY fault schedule that lets
    the run finish must reproduce it bit-for-bit."""
    import numpy as np
    w = np.arange(8.0, dtype='float32')
    for i in range(1, steps + 1):
        w = (w * np.float32(0.9)
             + np.float32(i) * np.ones(8, dtype='float32'))
    return w


def worker_main(args):
    """The supervised workload (internal --worker mode): deterministic
    toy training with a per-step sharded checkpoint, resumed from the
    latest committed step, with the FaultPlan's engine active."""
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu import telemetry
    from paddle_tpu.distributed.checkpoint import CheckpointManager
    from paddle_tpu.resilience import (
        install_shutdown, shutdown_requested, PREEMPTED_EXIT_CODE)
    from paddle_tpu.resilience.chaos import ChaosEngine, plan_from_env

    workdir = os.environ[DIR_ENV]
    steps = int(os.environ.get(STEPS_ENV, '12'))
    incarnation = int(os.environ.get('PADDLE_ELASTIC_RESTART_COUNT',
                                     '0'))
    preemptions = int(os.environ.get('PADDLE_ELASTIC_PREEMPT_COUNT',
                                     '0'))
    hb = os.path.join(workdir, 'heartbeat')
    telemetry.enable(os.path.join(workdir, 'telemetry'))
    plan = plan_from_env()
    if plan is not None and (incarnation or preemptions):
        # process-level faults fire once, in the FIRST incarnation —
        # a restarted worker re-reading the same plan must not
        # re-kill itself at the same step forever
        plan.faults = [f for f in plan.faults
                       if f.kind not in ('sigterm', 'sigkill')]
    engine = ChaosEngine(plan, heartbeat_file=hb) if plan else None
    if engine:
        engine.activate()
    install_shutdown()

    ckpt = os.path.join(workdir, 'ckpt')
    mgr = CheckpointManager(ckpt, keep=3, async_save=False)
    w = jnp.arange(8.0, dtype=jnp.float32)
    state = {'w': w, 'step': jnp.asarray(0)}
    restored, got = mgr.restore(state)
    start = 1
    if restored is not None:
        state = restored
        start = int(np.asarray(restored['step'])) + 1
    for i in range(start, steps + 1):
        if engine:
            engine.step(i)          # may SIGKILL/SIGTERM us right here
        state = {'w': state['w'] * jnp.float32(0.9)
                 + jnp.float32(i) * jnp.ones(8, jnp.float32),
                 'step': jnp.asarray(i)}
        mgr.save(state, i)
        with open(hb, 'a'):
            os.utime(hb, None)
        if shutdown_requested():
            mgr.wait()
            telemetry.dump_flight(os.path.join(
                workdir, f'flightrec-preempt-{i}.json'))
            sys.exit(PREEMPTED_EXIT_CODE)
    mgr.wait()
    with open(os.path.join(workdir, 'out.json'), 'w') as f:
        json.dump({'final_w': np.asarray(state['w']).tolist(),
                   'final_step': int(np.asarray(state['step'])),
                   'incarnation': incarnation,
                   'preemptions': preemptions}, f)
    return 0


def _load_events(workdir):
    """Every telemetry event of the run: streamed JSONL plus the event
    rings of any flight-recorder dumps (a SIGKILLed incarnation's last
    moments only survive in its pre-kill dump).  Shared with the
    multi-process ChaosCluster driver."""
    from paddle_tpu.resilience.chaos import load_run_events
    return load_run_events(workdir)


def supervise_run(plan, workdir, steps=12, max_restarts=3,
                  script=None, timeout=600):
    """Run the workload (or `script` argv) under `plan`; returns the
    report dict (ok, violations, injected, exit codes...)."""
    from paddle_tpu.distributed import elastic
    from paddle_tpu.resilience.chaos import check_invariants

    os.makedirs(workdir, exist_ok=True)
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = _REPO + os.pathsep + env.get('PYTHONPATH', '')
    env[DIR_ENV] = workdir
    env[STEPS_ENV] = str(steps)
    env['PADDLE_TPU_CHAOS_PLAN'] = plan.to_json()
    env['PADDLE_TPU_MIN_PREEMPT_UPTIME'] = '0'
    cmd = (list(script) if script
           else [sys.executable, os.path.abspath(__file__), '--worker'])

    events_seen = []
    exit_codes = {'preempt': [], 'exit': []}

    def on_event(kind, t):
        events_seen.append(kind)
        rc = t.proc.returncode if t.proc else None
        if kind in exit_codes and rc is not None:
            exit_codes[kind].append(rc)

    t0 = time.time()
    procs = elastic.start_local_trainers([cmd], envs=env)
    rc = elastic.watch_local_trainers(
        procs, max_restarts=max_restarts, poll=0.05,
        min_preempt_uptime=0.0, on_event=on_event,
        restart_backoff=0.2, restart_backoff_max=2.0)
    dur = time.time() - t0

    events = _load_events(workdir)
    injected = [e for e in events if e.get('kind') == 'fault_injected']
    violations = check_invariants(
        os.path.join(workdir, 'ckpt'), events=events,
        max_restarts=max_restarts, restarts=procs[0].restarts,
        preempt_codes=exit_codes['preempt'])
    if rc != 0:
        violations.append(f'run did not complete cleanly (rc={rc})')
    out_path = os.path.join(workdir, 'out.json')
    final = None
    if script is None:
        if os.path.exists(out_path):
            final = json.load(open(out_path))
            import numpy as np
            ref = _final_w(steps)
            if not np.allclose(final['final_w'], ref, rtol=0, atol=0):
                violations.append(
                    'final state differs from the uninterrupted '
                    'reference — a fault leaked into the arithmetic')
        else:
            violations.append('worker never wrote out.json')
    return {
        'ok': not violations,
        'violations': violations,
        'plan': json.loads(plan.to_json()),
        'steps': steps,
        'injected': [{k: e.get(k) for k in
                      ('fault', 'step', 'path', 'seq', 'errno')
                      if e.get(k) is not None} for e in injected],
        'incarnations': 1 + procs[0].restarts + procs[0].preemptions,
        'failure_restarts': procs[0].restarts,
        'preemptions': procs[0].preemptions,
        'preempt_exit_codes': exit_codes['preempt'],
        'supervisor_events': events_seen,
        'duration_s': round(dur, 2),
        'final': final,
    }


def main(argv=None):
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == '--worker':
        sys.exit(worker_main(argv[1:]))
    ap = argparse.ArgumentParser(
        prog='chaos_run',
        description='Run a training workload under a seeded FaultPlan '
                    'and assert the resilience invariants.')
    ap.add_argument('--plan', default=None,
                    help='FaultPlan JSON (inline or a file path); '
                         'default: the built-in kill+torn-write plan')
    ap.add_argument('--steps', type=int, default=None,
                    help='training steps (default 12; 10 in --smoke)')
    ap.add_argument('--max-restarts', type=int, default=3)
    ap.add_argument('--dir', default=None,
                    help='workdir (default: a fresh temp dir)')
    ap.add_argument('--smoke', action='store_true',
                    help='CI gate mode: default plan, fewer steps')
    ap.add_argument('--json', action='store_true',
                    help='machine-readable report on stdout')
    ap.add_argument('--script', nargs=argparse.REMAINDER, default=None,
                    help='run this argv as the worker instead of the '
                         'built-in workload (plan ships via '
                         'PADDLE_TPU_CHAOS_PLAN)')
    args = ap.parse_args(argv)

    from paddle_tpu.resilience.chaos import FaultPlan
    if args.plan and not args.smoke:
        text = args.plan
        if os.path.exists(text):
            text = open(text).read()
        plan = FaultPlan.from_json(text)
    else:
        # --smoke is the CI gate: always the built-in plan (a custom
        # --plan is ignored so the gate's coverage can't be narrowed
        # by accident) and a shorter run
        plan = FaultPlan.from_json(json.dumps(DEFAULT_PLAN))
    steps = args.steps if args.steps is not None else \
        (10 if args.smoke else 12)
    workdir = args.dir
    if workdir is None:
        import tempfile
        workdir = tempfile.mkdtemp(prefix='chaos_run_')
    report = supervise_run(plan, workdir, steps=steps,
                           max_restarts=args.max_restarts,
                           script=args.script)
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print(f'chaos_run: plan={plan.name or "custom"} '
              f'seed={plan.seed} steps={steps} '
              f'workdir={workdir}')
        for e in report['injected']:
            print(f'  injected: {e}')
        print(f'  incarnations={report["incarnations"]} '
              f'(failure restarts {report["failure_restarts"]}, '
              f'preemptions {report["preemptions"]}) '
              f'in {report["duration_s"]}s')
        if report['ok']:
            print('  all resilience invariants held')
        else:
            for v in report['violations']:
                print(f'  VIOLATION: {v}')
    return 0 if report['ok'] else 1


if __name__ == '__main__':
    sys.exit(main())
