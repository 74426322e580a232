#!/usr/bin/env python
"""BERT masked-LM pretraining steps as a user script: fused MLM head (no [B·T, V] logits tensor), bf16 AMP O2,
whole step in one XLA module.

    python examples/bert_pretrain.py                 # tiny config
    python examples/bert_pretrain.py --size base --seq-len 128
"""
import argparse
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.models.bert import bert_base, bert_tiny
from paddle_tpu.parallel import ParallelTrainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--size', choices=('tiny', 'base'), default='tiny')
    ap.add_argument('--steps', type=int, default=4)
    ap.add_argument('--batch-size', type=int, default=8)
    ap.add_argument('--seq-len', type=int, default=64)
    ap.add_argument('--mask-rate', type=float, default=0.15)
    args = ap.parse_args()

    paddle.seed(0)
    if args.size == 'base':
        model = bert_base(max_seq_len=args.seq_len, dropout=0.0,
                          fused_head=True)
    else:
        model = bert_tiny(fused_head=True,
                          max_seq_len=max(128, args.seq_len))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs['use_pure_fp16'] = True
    trainer = ParallelTrainer(model, opt,
                              lambda out, y: model.loss(out, y),
                              strategy=strategy)

    rs = np.random.RandomState(0)
    V = model.config.vocab_size
    ids = rs.randint(0, V, size=(args.batch_size,
                                 args.seq_len)).astype('int64')
    # MLM labels: predict mask-rate of positions, ignore the rest
    lbl = np.where(rs.rand(*ids.shape) < args.mask_rate,
                   rs.randint(0, V, size=ids.shape), -100).astype('int64')
    for i in range(args.steps):
        t0 = time.time()
        loss = trainer.step(ids, lbl)
        print(f'step {i}: mlm_loss={float(np.asarray(loss)):.4f} '
              f'({time.time() - t0:.2f}s)')


if __name__ == '__main__':
    main()
