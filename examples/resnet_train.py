#!/usr/bin/env python
"""ResNet-50 bf16(AMP O2) training, written the way a user would:
DataLoader feeding a ParallelTrainer whose whole fwd+bwd+update step is ONE XLA module.

    python examples/resnet_train.py [--steps 30] [--batch-size 256]
    python examples/resnet_train.py --depth 18 --image 64  # small run

--space-to-depth enables the MLPerf-TPU stem (exact same function,
tests/test_vision_text.py; its speed on the chip is not measured)."""
import argparse
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import fleet
from paddle_tpu.io import DataLoader, TensorDataset
from paddle_tpu.parallel import ParallelTrainer
from paddle_tpu.vision.models.resnet import (ResNet, BasicBlock,
                                             BottleneckBlock)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=30)
    ap.add_argument('--batch-size', type=int, default=256)
    ap.add_argument('--depth', type=int, default=50,
                    choices=(18, 34, 50, 101, 152))
    ap.add_argument('--image', type=int, default=224)
    ap.add_argument('--classes', type=int, default=1000)
    ap.add_argument('--space-to-depth', action='store_true')
    args = ap.parse_args()

    paddle.seed(0)
    block = BottleneckBlock if args.depth >= 50 else BasicBlock
    net = ResNet(block, args.depth, num_classes=args.classes,
                 data_format='NHWC',
                 stem_space_to_depth=args.space_to_depth)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=net.parameters())
    ce = nn.CrossEntropyLoss()
    strategy = fleet.DistributedStrategy()
    strategy.amp = True                              # bf16 compute
    strategy.amp_configs['use_pure_fp16'] = True     # O2
    trainer = ParallelTrainer(net, opt, lambda out, y: ce(out, y),
                              strategy=strategy)

    rs = np.random.RandomState(0)
    n = args.batch_size * 4
    ds = TensorDataset([
        rs.randn(n, args.image, args.image, 3).astype('float32'),
        rs.randint(0, args.classes, size=(n, 1)).astype('int64')])
    loader = DataLoader(ds, batch_size=args.batch_size, shuffle=True,
                        drop_last=True, num_workers=2, to_tensor=False)

    done = 0
    t_start = 0
    t0 = time.time()
    while done < args.steps:
        for x, y in loader:
            loss = trainer.step(x, y)
            done += 1
            if done == 1:
                # first step includes the XLA compile; restart timing
                print(f'compile+step1: {time.time() - t0:.1f}s '
                      f'loss={float(np.asarray(loss)):.4f}')
                t0, t_start = time.time(), done
            if done >= args.steps:
                break
    dt = time.time() - t0
    steps = done - t_start
    if steps > 0:
        print(f'{steps} steps in {dt:.2f}s -> '
              f'{args.batch_size * steps / dt:.0f} imgs/s')


if __name__ == '__main__':
    main()
