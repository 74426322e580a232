#!/usr/bin/env python
"""profile_run — capture → parse → emit → (optionally) fit, in one
shot: the self-profiling loop's end-to-end driver.

Runs a built-in data-parallel workload on whatever devices jax finds
(with JAX_PLATFORMS=cpu: a dp=8 virtual CPU mesh, no chip needed),
with the sampled profiler (``telemetry.profile``) capturing a trace window
mid-training.  Profiled collectives are census-matched against the
compiled module and land as real ``collective_observed`` telemetry —
**zero hand-written fixtures** — which:

* ``tools/run_report.py`` joins into populated observed_us / us_ratio
  columns (plan + collectives sections), and
* ``tools/calibrate_costmodel.py`` fits into a calibration table the
  auto-sharding planner consumes (``--fit calibration.json`` does the
  fit right here).

That closes the loop the PR-4/6 cost model opened: predict (planner)
→ measure (this driver) → re-calibrate (the fitted table) → predict
better.

    JAX_PLATFORMS=cpu python tools/profile_run.py      # CPU mesh, report
    python tools/profile_run.py --fit calibration.json # + fit the table
    python tools/profile_run.py --json                 # run_report schema
    python tools/profile_run.py --model lenet --dp 8 --steps 16

Exit codes: 0 = profiled collectives landed; 1 = the run produced no
``collective_observed`` events (the loop did NOT close); 2 = bad args.
"""
import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog='profile_run',
        description='Capture an on-device trace window over a built-in '
                    'dp-mesh workload, emit collective_observed '
                    'telemetry, and optionally fit a calibration '
                    'table from it.')
    ap.add_argument('--model', choices=('mlp', 'lenet'), default='mlp',
                    help='built-in workload (default mlp: fast '
                         'compile, real dp all-reduces)')
    ap.add_argument('--dp', type=int, default=8,
                    help='data-parallel mesh size (default 8; forced '
                         'virtual CPU devices when no multi-device '
                         'backend is configured; 0 = all visible '
                         'devices — the chip-session posture)')
    ap.add_argument('--batch', type=int, default=None,
                    help='global batch (default: model-specific)')
    ap.add_argument('--steps', type=int, default=10,
                    help='train steps to run (default 10)')
    ap.add_argument('--start', type=int, default=3,
                    help='first profiled step (default 3 — past '
                         'compile/warmup)')
    ap.add_argument('--window', type=int, default=2,
                    help='steps per capture window (default 2)')
    ap.add_argument('--every', type=int, default=100,
                    help='steps between window starts (default 100: '
                         'one window in a short run)')
    ap.add_argument('--out', default=None,
                    help='output dir for telemetry JSONL + trace '
                         'artifacts (default: a fresh temp dir)')
    ap.add_argument('--fit', metavar='CALIBRATION_JSON', default=None,
                    help='after the run, fit a costmodel calibration '
                         'table from the emitted events '
                         '(tools/calibrate_costmodel.py) to this path')
    ap.add_argument('--calibration', default=None,
                    help='existing calibration table to load for the '
                         'PREDICTED side (A/B a previous fit)')
    ap.add_argument('--no-plan', action='store_true',
                    help='skip the auto-sharding planner (no '
                         'plan_selected event; collectives_cmp still '
                         'populates)')
    ap.add_argument('--json', action='store_true',
                    help='print the full run_report --json document')
    return ap.parse_args(argv)


def _force_virtual_mesh(dp):
    """A dp>1 run that was ASKED onto the CPU (JAX_PLATFORMS=cpu) gets
    XLA's virtual host devices — set BEFORE jax imports.  An unset
    variable is what a plain TPU install looks like: there the run
    keeps the real devices."""
    if os.environ.get('JAX_PLATFORMS') != 'cpu':
        return
    flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + f' --xla_force_host_platform_device_count={dp}'
        ).strip()


def build_workload(model_name, batch, dp):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    rs = np.random.RandomState(0)
    if model_name == 'lenet':
        from paddle_tpu.vision.models import LeNet
        net = LeNet()
        loss = nn.CrossEntropyLoss()
        b = batch or 8 * dp
        x = rs.randn(b, 1, 28, 28).astype('float32')
        y = rs.randint(0, 10, size=(b, 1)).astype('int64')
    else:
        net = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                            nn.Linear(128, 16))
        loss = nn.MSELoss()
        b = batch or 16 * dp
        x = rs.randn(b, 64).astype('float32')
        y = rs.randn(b, 16).astype('float32')
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    return net, opt, loss, x, y


def main(argv=None):
    args = parse_args(argv)
    args.steps = max(1, args.steps)
    if args.dp > 0:
        _force_virtual_mesh(args.dp)
    out = os.path.abspath(args.out or tempfile.mkdtemp(
        prefix='profile_run_'))
    os.makedirs(out, exist_ok=True)

    import jax
    from paddle_tpu import telemetry
    from paddle_tpu.parallel import ParallelTrainer
    from paddle_tpu.distributed import env as dist_env

    n_dev = len(jax.devices())
    dp = args.dp if args.dp > 0 else n_dev
    if n_dev < dp:
        print(f'profile_run: only {n_dev} devices for --dp {dp}',
              file=sys.stderr)
        return 2
    print(f'profile_run: {args.model} on dp={dp} '
          f'({jax.devices()[0].platform}), out={out}', file=sys.stderr)

    telemetry.enable(out)
    prev_mesh = dist_env.get_mesh()
    mesh = dist_env.build_mesh({'dp': dp})
    dist_env.set_mesh(mesh)
    try:
        net, opt, loss_fn, x, y = build_workload(
            args.model, args.batch, dp)
        schedule = telemetry.ProfileSchedule(
            every=args.every, steps=args.window, start=args.start,
            dir=out)
        tr = ParallelTrainer(
            net, opt, lambda o, t: loss_fn(o, t), mesh=mesh,
            auto_shard=not args.no_plan, profile=schedule,
            calibration=args.calibration)
        for _ in range(args.steps):
            loss = tr.step(x, y)
        jax.block_until_ready(loss)
        windows = tr.finish_profile(sync=loss)
        observed = telemetry.events('collective_observed')
    finally:
        dist_env.set_mesh(prev_mesh)
        telemetry.disable()

    # -- join through run_report (the artifact consumers see) ------------
    import run_report as rr
    jsonls, flights = rr.discover([out])
    events, sources, skew = rr.load_events(jsonls, flights)
    report = rr.analyze(events, sources, skew)
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        rr.render(report)

    n_ratio = sum(1 for row in (report.get('collectives_cmp')
                                or {}).values() if row.get('us_ratio'))
    print(f'profile_run: {len(windows)} window(s), '
          f'{len(observed)} collective_observed event(s), '
          f'{n_ratio} op(s) with us_ratio', file=sys.stderr)
    ok = bool(observed)
    if not ok and dp <= 1:
        # a single-device session has no collectives to observe; the
        # capture/breakdown evidence alone is the success there
        print('profile_run: single-device run — no collectives to '
              'observe (capture breakdown only)', file=sys.stderr)
        ok = True

    if args.fit and not observed:
        # visible, even when the run counts as ok (dp<=1): a consumer
        # expecting a fresh table must not mistake silence for success
        print(f'profile_run: --fit {args.fit} SKIPPED — no '
              'collective_observed samples to fit from',
              file=sys.stderr)
    if ok and args.fit and observed:
        import calibrate_costmodel as cc
        rc = cc.main([out, '-o', args.fit])
        if rc != 0:
            print(f'profile_run: calibration fit failed (rc={rc})',
                  file=sys.stderr)
            ok = False
        else:
            print(f'profile_run: calibration table written to '
                  f'{args.fit}', file=sys.stderr)
    if not ok and not observed:
        print('profile_run: NO collective_observed events were '
              'produced — the predicted-vs-observed loop did not '
              'close (check the profile_capture events for errors)',
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
