#!/usr/bin/env python
"""serve_fleet — multi-replica serving: N engine workers behind ONE
door (paddle_tpu/serving/router.py).

    # one replica worker (what the router spawns; also usable alone)
    python tools/serve_fleet.py worker --config serve.json \\
        --port-file /tmp/r0.port [--warmup] [--host 127.0.0.1]

    # a whole fleet: N active replicas + S warm spares + the door
    python tools/serve_fleet.py up --config serve.json \\
        --replicas 2 --spares 1 [--port 8901] [--workdir DIR]

CONFIG is the same JSON ``tools/precompile.py --serve`` reads:
ServeConfig fields plus ``"model"`` ('tiny' | 'small') and
``"model_kwargs"``.  Workers run with the repo on PYTHONPATH and
INHERIT their JAX platform from this launcher, which sets none and
initialises no backend itself: on a TPU machine the workers serve
from the chip.  A chip belongs to one process, so there the fleet is
one replica per chip (``--replicas 1`` on a one-chip machine).  Each
worker publishes ``{"port": ..., "pid": ...}`` through its
--port-file once ``/healthz`` answers, which is the router's
readiness handshake.

``up`` binds the door to 127.0.0.1 by default — same posture as the
single-engine frontend; set PADDLE_TPU_FRONTEND_HOST to widen.
Requests that hit the door survive replica death mid-stream: the
router replays prompt+emitted-prefix on a survivor and the
per-request position-keyed sampling discipline makes the resumed
stream bit-exact (see README "Serving front door").

Exit codes: 0 clean shutdown, 1 startup failure, 2 usage error.
"""
import argparse
import json
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_config(path):
    with open(path) as f:
        return json.load(f)


def build_engine(doc):
    """Model + engine from a serve-config document — the exact
    builder ``precompile --serve`` uses, so a fleet worker's
    fingerprints match the AOT-warmed cache."""
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt as _gpt
    from paddle_tpu.serving import ServeConfig, ServingEngine
    builders = {'tiny': _gpt.gpt_tiny, 'small': _gpt.gpt_small}
    name = doc.get('model', 'tiny')
    if name not in builders:
        raise SystemExit(f'unknown model {name!r} '
                         f'(have {sorted(builders)})')
    paddle.seed(0)
    kw = dict(doc.get('model_kwargs') or {})
    kw.setdefault('dropout', 0.0)
    model = builders[name](**kw)
    model.eval()
    return ServingEngine(model, ServeConfig.from_json(doc))


def run_worker(args):
    from paddle_tpu.serving.frontend import ServingFrontend
    doc = _load_config(args.config)
    engine = build_engine(doc)
    if args.warmup:
        engine.warmup()
    fe = ServingFrontend(engine, port=args.port,
                         host=args.host).start()
    if args.port_file:
        tmp = args.port_file + '.tmp'
        with open(tmp, 'w') as f:
            json.dump({'port': fe.port, 'pid': os.getpid()}, f)
        os.replace(tmp, args.port_file)   # atomic: no partial reads
    print(f'[serve_fleet] worker ready on {fe.url}', flush=True)
    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    fe.stop()
    return 0


def launch_fleet(config_path, replicas=2, spares=0, workdir=None,
                 warmup_spares=True, extra_env=None):
    """Spawn the worker set and return a started
    (:class:`FleetRouter`, handles) pair — the importable form
    tests/test_engine_frontdoor.py::TestRealFleetE2E uses."""
    from paddle_tpu.serving.router import FleetRouter, ReplicaHandle
    workdir = workdir or os.path.join('.', '_fleet')
    active, warm = [], []
    for i in range(replicas):
        active.append(ReplicaHandle.spawn(
            f'r{i}', config_path, workdir, extra_env=extra_env))
    for i in range(spares):
        warm.append(ReplicaHandle.spawn(
            f's{i}', config_path, workdir, warmup=warmup_spares,
            extra_env=extra_env))
    try:
        for rep in active + warm:
            rep.wait_ready()
    except Exception:
        for rep in active + warm:
            rep.kill()
        raise
    return FleetRouter(active, spares=warm)


def run_up(args):
    from paddle_tpu.serving.frontend import FRONTEND_HOST_ENV
    from paddle_tpu.serving.router import FleetFrontend
    host = os.environ.get(FRONTEND_HOST_ENV, '127.0.0.1')
    try:
        router = launch_fleet(args.config, replicas=args.replicas,
                              spares=args.spares,
                              workdir=args.workdir)
    except Exception as e:
        print(f'[serve_fleet] fleet failed to start: {e!r}',
              file=sys.stderr)
        return 1
    router.start_health_loop()
    door = FleetFrontend(router, port=args.port, host=host).start()
    print(f'[serve_fleet] door open on {door.url} '
          f'({args.replicas} replicas, {args.spares} spares)',
          flush=True)
    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    door.stop()
    router.stop()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='serve_fleet',
        description='multi-replica serving fleet (worker + door)')
    sub = ap.add_subparsers(dest='cmd', required=True)

    w = sub.add_parser('worker', help='one engine replica')
    w.add_argument('--config', required=True,
                   help='serve-config JSON (precompile --serve form)')
    w.add_argument('--port-file',
                   help='publish {"port", "pid"} here once ready')
    w.add_argument('--port', type=int, default=0)
    w.add_argument('--host', default='127.0.0.1')
    w.add_argument('--warmup', action='store_true',
                   help='run engine.warmup() before opening the door')

    u = sub.add_parser('up', help='N replicas + spares + the door')
    u.add_argument('--config', required=True)
    u.add_argument('--replicas', type=int, default=2)
    u.add_argument('--spares', type=int, default=0)
    u.add_argument('--port', type=int, default=0)
    u.add_argument('--workdir', default=None)

    args = ap.parse_args(argv)
    if args.cmd == 'worker':
        return run_worker(args)
    return run_up(args)


if __name__ == '__main__':
    sys.exit(main())
