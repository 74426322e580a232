#!/usr/bin/env python
"""quant_accuracy — bit-accuracy convergence harness for the
quantized wire.

Trains the SAME model twice on the SAME data and rng stream — once
full width, once with ``ParallelTrainer(quant_collectives='int8')`` —
and gates on the final-loss delta: the EQuARX claim is 2-4x wire
reduction at negligible quality loss, and this harness is the
"negligible" half of that claim, runnable on the CPU smoke before any
chip time is spent.  The wire half rides along: each trainer's
compiled module is censused (analysis.hlo.collective_census) so the
report carries measured predicted-wire bytes per dtype, and
tests/test_engine_quant_wire.py joins the same evidence through
run_report.

    python tools/quant_accuracy.py                   # lenet + gpt
    python tools/quant_accuracy.py --steps 60 --json
    python tools/quant_accuracy.py --master-accum    # exact-sum mode

Exit 0 iff every gate holds (loss deltas within --gate-rel, wire
reduction >= --gate-wire).
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# an 8-device virtual CPU mesh, forced BEFORE jax import (same posture
# as tests/conftest.py): this tool compares numerics, not devices
os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ.setdefault('PADDLE_TPU_COMPILE_CACHE', '0')

import numpy as np  # noqa: E402


def _census(trainer, mesh):
    """Per-op predicted wire bytes (+ dtype tags) of the compiled
    step."""
    from paddle_tpu.analysis import hlo as _hlo
    census = _hlo.collective_census(
        _hlo.parse_module(trainer.compiled_text()),
        mesh_shape=dict(mesh.shape))
    return {
        'per_op': {op: {'calls': r['calls'],
                        'wire_bytes': r['wire_bytes'],
                        'wire_dtype': r.get('wire_dtype')}
                   for op, r in census.items()},
        'wire_bytes_total': sum(r['wire_bytes']
                                for r in census.values()),
    }


def _run(make_model, make_batch, loss_fn, *, quant, steps, seed,
         n_inputs=1, profile=None):
    """One training run; returns losses + wire census + compile
    counts."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import telemetry
    from paddle_tpu.parallel import ParallelTrainer
    from paddle_tpu.distributed import env as dist_env

    prev = dist_env.get_mesh()
    mesh = dist_env.build_mesh({'dp': 8})
    dist_env.set_mesh(mesh)
    try:
        paddle.seed(seed)
        model = make_model()
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        tr = ParallelTrainer(model, opt, loss_fn, mesh=mesh,
                             n_inputs=n_inputs,
                             quant_collectives=quant, profile=profile)
        batch = make_batch()
        losses = []
        compiles0 = len(telemetry.events('compile')) \
            if telemetry.active() else 0
        for i in range(steps):
            losses.append(float(np.asarray(tr.step(*batch))))
        jax.block_until_ready(losses[-1])
        if profile is not None:
            tr.finish_profile(sync=losses[-1])
        compiles = (len(telemetry.events('compile')) - compiles0) \
            if telemetry.active() else None
        out = {
            'final_loss': losses[-1],
            'first_loss': losses[0],
            'losses': [round(v, 6) for v in losses],
            'quant': (vars(tr._quant_active)
                      if tr._quant_active is not None else None),
            'compile_events': compiles,
            'census': _census(tr, mesh),
        }
        return out
    finally:
        dist_env.set_mesh(prev)


def run_lenet(quant=None, steps=40, seed=0, profile=None):
    """LeNet on synthetic MNIST-shaped data, dp=8."""
    from paddle_tpu import nn
    from paddle_tpu.vision.models import LeNet
    rs = np.random.RandomState(0)
    x = rs.randn(64, 1, 28, 28).astype('float32')
    y = rs.randint(0, 10, size=(64, 1)).astype('int64')
    ce = nn.CrossEntropyLoss()
    return _run(LeNet, lambda: (x, y), lambda o, t: ce(o, t),
                quant=quant, steps=steps, seed=seed, profile=profile)


def run_gpt(quant=None, steps=8, seed=0, profile=None):
    """gpt-tiny causal LM, a few steps, dp=8."""
    from paddle_tpu.models.gpt import gpt_tiny
    rs = np.random.RandomState(0)
    holder = {}

    def make():
        m = holder['m'] = gpt_tiny(max_seq_len=32)
        return m

    ids = None

    def batch():
        nonlocal ids
        if ids is None:
            V = holder['m'].config.vocab_size
            ids = rs.randint(0, V, size=(16, 32)).astype('int64')
        return (ids, ids)

    return _run(make, batch, lambda o, y: holder['m'].loss(o, y),
                quant=quant, steps=steps, seed=seed, profile=profile)


def compare(target, quant_cfg, steps, seed=0, profile=None):
    """Full-width vs quantized run of one target; returns the joined
    evidence row."""
    runner = {'lenet': run_lenet, 'gpt': run_gpt}[target]
    # quant=False, not None: None means "the env decides", and an
    # ambient PADDLE_TPU_QUANT_COLLECTIVES would silently quantize
    # the BASELINE too — the gate would then compare quantized vs
    # quantized and report the wire as pointless
    full = runner(quant=False, steps=steps, seed=seed)
    q = runner(quant=quant_cfg, steps=steps, seed=seed,
               profile=profile)
    fw = full['census']['wire_bytes_total']
    qw = max(1, q['census']['wire_bytes_total'])
    delta = abs(q['final_loss'] - full['final_loss'])
    denom = max(abs(full['first_loss'] - full['final_loss']), 1e-9)
    return {
        'target': target,
        'final_loss_full': full['final_loss'],
        'final_loss_quant': q['final_loss'],
        'loss_delta': round(delta, 6),
        # delta relative to the loss PROGRESS full-width made — "the
        # quantized run reached the same place", scale-free across
        # targets
        'loss_delta_rel': round(delta / denom, 6),
        'wire_bytes_full': fw,
        'wire_bytes_quant': qw,
        'wire_reduction': round(fw / qw, 3),
        'quant_active': q['quant'],
        'census_full': full['census']['per_op'],
        'census_quant': q['census']['per_op'],
        'compile_events_quant': q['compile_events'],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='quantized-wire vs full-width convergence gate')
    ap.add_argument('--targets', default='lenet,gpt')
    ap.add_argument('--steps', type=int, default=40,
                    help='lenet steps (gpt runs max(8, steps//5))')
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--block', type=int, default=256)
    ap.add_argument('--master-accum', action='store_true')
    ap.add_argument('--no-stochastic', action='store_true')
    ap.add_argument('--gate-rel', type=float, default=0.10,
                    help='max |final-loss delta| as a fraction of the '
                         'full-width loss progress')
    ap.add_argument('--gate-wire', type=float, default=2.0,
                    help='min full/quant predicted-wire-byte ratio')
    ap.add_argument('--json', action='store_true')
    args = ap.parse_args(argv)

    quant_cfg = {'block': args.block, 'min_bytes': 0,
                 'master_accum': args.master_accum,
                 'stochastic': not args.no_stochastic}
    rows = []
    failures = []
    for target in args.targets.split(','):
        target = target.strip()
        steps = args.steps if target == 'lenet' \
            else max(8, args.steps // 5)
        row = compare(target, quant_cfg, steps, seed=args.seed)
        rows.append(row)
        if row['loss_delta_rel'] > args.gate_rel:
            failures.append(
                f'{target}: quantized final loss drifted '
                f'{row["loss_delta_rel"] * 100:.1f}% of full-width '
                f'progress (gate {args.gate_rel * 100:.0f}%): '
                f'{row["final_loss_full"]:.5f} vs '
                f'{row["final_loss_quant"]:.5f}')
        if row['wire_reduction'] < args.gate_wire:
            failures.append(
                f'{target}: wire reduction x{row["wire_reduction"]} '
                f'below the x{args.gate_wire} gate')
        if not row['quant_active']:
            failures.append(f'{target}: quantized wire never armed '
                            '(trainer fell back to full width)')
    doc = {'ok': not failures, 'failures': failures, 'rows': rows,
           'config': quant_cfg}
    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        for r in rows:
            print(f'{r["target"]}: full {r["final_loss_full"]:.5f} '
                  f'quant {r["final_loss_quant"]:.5f} '
                  f'(delta {r["loss_delta_rel"] * 100:.2f}% of '
                  f'progress), wire x{r["wire_reduction"]} '
                  f'({r["wire_bytes_full"]:,} -> '
                  f'{r["wire_bytes_quant"]:,} B)')
        for f in failures:
            print(f'FAIL: {f}')
        if not failures:
            print('ok')
    return 0 if not failures else 1


if __name__ == '__main__':
    sys.exit(main())
