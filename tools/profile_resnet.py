#!/usr/bin/env python
"""Profile the ResNet-50 bench step on the real TPU chip.

Dumps: compiled cost analysis (flops), optimized-HLO op census (via
the shared ``profiler.op_summary`` / ``analysis.hlo`` parser — the
ad-hoc regex census this script used to carry is gone), and timed
variants (fwd-only, fwd+bwd, full step) to locate where step time
goes.  Findings feed bench.py / PERF.md (VERDICT round-1 item 3).

``--emit-telemetry`` additionally captures an on-device trace window
around the timed full-step loop through the shared capture/parse API
(``telemetry.capture``): the run leaves telemetry JSONL + a
``profile_capture`` event (device-compute vs collective breakdown,
census-matched ``collective_observed`` on multi-device runs) in
``--out``, joinable by tools/run_report.py and fittable by
tools/calibrate_costmodel.py.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--batch', type=int, default=256)
    p.add_argument('--image', type=int, default=224)
    p.add_argument('--iters', type=int, default=20)
    p.add_argument('--emit-telemetry', action='store_true',
                   help='capture a trace window around the timed loop '
                        'and stream telemetry JSONL to --out')
    p.add_argument('--out', default=os.path.join(
        'chiprun_out', 'profile_resnet'),
        help='telemetry/trace output dir for --emit-telemetry')
    args = p.parse_args()

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import nn, telemetry
    from paddle_tpu.vision.models.resnet import ResNet, BottleneckBlock
    from paddle_tpu.parallel import ParallelTrainer
    from paddle_tpu.distributed import fleet

    log(f'device: {jax.devices()[0]}')
    if args.emit_telemetry:
        telemetry.enable(args.out)
    paddle.seed(0)
    net = ResNet(BottleneckBlock, 50, num_classes=1000, data_format='NHWC')
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=net.parameters())
    ce = nn.CrossEntropyLoss()
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs['use_pure_fp16'] = True
    trainer = ParallelTrainer(net, opt, lambda out, y: ce(out, y),
                              strategy=strategy)

    rs = np.random.RandomState(0)
    x = jax.device_put(rs.randn(args.batch, args.image, args.image, 3)
                       .astype('float32'))
    y = jax.device_put(rs.randint(0, 1000, size=(args.batch, 1))
                       .astype('int64'))

    # one step to build + place state
    loss = trainer.step(x, y)
    jax.block_until_ready(loss)

    # per-op census + module cost totals through the ONE shared
    # lowering (trainer.compiled_text memo feeds op_summary, the
    # collective census and memory_usage alike)
    try:
        trainer.op_summary(x, y, top=40, stream=sys.stderr)
    except Exception as e:
        log('op_summary failed:', repr(e))
    try:
        txt = trainer.compiled_text()
        log('--- conv lines (first 10) ---')
        shown = 0
        for line in txt.splitlines():
            if ' convolution(' in line and shown < 10:
                log(line.strip()[:200])
                shown += 1
    except Exception as e:
        log('hlo text unavailable:', repr(e))

    # timed: full step — NEVER traced: in-window tracing adds
    # per-step overhead (PERF.md) and this number is the headline
    t0 = time.time()
    for _ in range(args.iters):
        loss = trainer.step(x, y)
    jax.block_until_ready(loss)
    full = (time.time() - t0) / args.iters
    log(f'full step: {full * 1000:.2f} ms '
        f'({args.batch / full:.0f} imgs/s)')

    if args.emit_telemetry:
        # a SEPARATE short traced window, after the headline loop
        n_trace = min(args.iters, 4)
        mesh_shape = (dict(trainer.mesh.shape)
                      if trainer.mesh is not None else None)
        with telemetry.capture(
                os.path.join(args.out, 'trace'), name='resnet',
                hlo_text_fn=trainer.compiled_text,
                mesh_shape=mesh_shape, steps=n_trace) as cap:
            for _ in range(n_trace):
                loss = trainer.step(x, y)
            cap.sync = loss
        win = cap.windows[-1] if cap.windows else {}
        log(f'trace window ({n_trace} steps): '
            f'{win.get("device_us_per_step", 0):.0f} us/step device, '
            f'{win.get("collective_us_per_step", 0):.0f} us '
            'collectives '
            f'({len(cap.observed)} collective_observed)')

    # fwd-only (same AMP path), jitted separately
    from paddle_tpu.jit import functional_call
    from paddle_tpu import amp as amp_mod

    params, buffers = net.functional_state()

    def fwd(params, x):
        with amp_mod.auto_cast(level='O2'):
            out, _ = functional_call(net, params, buffers, (x,),
                                     training=True,
                                     key=jax.random.PRNGKey(0))
        return out.astype(jnp.float32).mean()

    jf = jax.jit(fwd)
    jf(params, x).block_until_ready()
    t0 = time.time()
    for _ in range(args.iters):
        r = jf(params, x)
    r.block_until_ready()
    fwd_t = (time.time() - t0) / args.iters
    log(f'fwd-only: {fwd_t * 1000:.2f} ms')

    # fwd+bwd (no optimizer)
    jg = jax.jit(jax.grad(fwd))
    jg(params, x)
    jax.block_until_ready(jg(params, x))
    t0 = time.time()
    for _ in range(args.iters):
        g = jg(params, x)
    jax.block_until_ready(g)
    bwd_t = (time.time() - t0) / args.iters
    log(f'fwd+bwd: {bwd_t * 1000:.2f} ms')
    log(f'optimizer+overhead: {(full - bwd_t) * 1000:.2f} ms')
    if args.emit_telemetry:
        telemetry.disable()
        log(f'telemetry JSONL + trace artifacts: {args.out}')


if __name__ == '__main__':
    main()
