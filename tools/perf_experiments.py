#!/usr/bin/env python
"""ResNet-50 perf experiment matrix (PERF.md follow-ups).

The step is HBM-bound; each variant tests one bytes-reduction lever:
  base      — bench.py config (batch 256, bf16 AMP O2)
  remat     — strategy.recompute: trade recompute FLOPs for residuals
  bf16in    — feed the images as bf16 (halves the input slab)
  b512      — batch 512 (amortize fixed traffic; may OOM)
  s2d       — MLPerf-TPU space-to-depth stem (4x4/s1 conv on the
              block-2 s2d input; exact-function re-lay of the 7x7/s2
              stem — parity locked in test_resnet_s2d_stem_matches_
              standard; this measures whether it is FASTER)
Run on the real chip: python tools/perf_experiments.py
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(tag, batch=256, image=224, recompute=False, bf16_in=False,
        s2d=False, iters=30, warmup=5):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models.resnet import ResNet, BottleneckBlock
    from paddle_tpu.parallel import ParallelTrainer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import env as dist_env

    dist_env.set_mesh(None)
    paddle.seed(0)
    net = ResNet(BottleneckBlock, 50, num_classes=1000,
                 data_format='NHWC', stem_space_to_depth=s2d)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=net.parameters())
    ce = nn.CrossEntropyLoss()
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs['use_pure_fp16'] = True
    strategy.recompute = recompute
    trainer = ParallelTrainer(net, opt, lambda out, y: ce(out, y),
                              strategy=strategy)
    rs = np.random.RandomState(0)
    x = rs.randn(batch, image, image, 3)
    x = jax.device_put(x.astype('bfloat16' if bf16_in else 'float32'))
    y = jax.device_put(rs.randint(0, 1000, size=(batch, 1))
                       .astype('int64'))
    try:
        loss = None
        for _ in range(warmup):
            loss = trainer.step(x, y)
        jax.block_until_ready(loss)
        t0 = time.time()
        for _ in range(iters):
            loss = trainer.step(x, y)
        lv = float(np.asarray(loss))
        dt = (time.time() - t0) / iters
        print(f'{tag:8s} {dt * 1000:7.1f} ms/step '
              f'{batch / dt:8.0f} imgs/s  loss={lv:.3f}', flush=True)
        return batch / dt
    except Exception as e:
        print(f'{tag:8s} FAILED: {type(e).__name__}: {e}', flush=True)
        return None


def main():
    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    print('device:', jax.devices()[0], flush=True)
    results = {}
    results['base'] = run('base')
    results['remat'] = run('remat', recompute=True)
    results['bf16in'] = run('bf16in', bf16_in=True)
    results['b512'] = run('b512', batch=512)
    results['b512rm'] = run('b512rm', batch=512, recompute=True)
    results['s2d'] = run('s2d', s2d=True)
    results['s2d_bf16'] = run('s2d_bf16', s2d=True, bf16_in=True)
    best = max((v, k) for k, v in results.items() if v)
    print(f'best: {best[1]} at {best[0]:.0f} imgs/s')


if __name__ == '__main__':
    main()
