"""Profiler op-level summary (reference python/paddle/fluid/
profiler.py prints a per-op table via stop_profiler(sorted_key);
VERDICT r4 task 8).  Here the rows come from the compiled step's
optimized HLO — post-fusion opcodes ranked by output-byte traffic."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, profiler


class TestOpSummary:
    def test_renders_for_resnet_bench_step(self, capsys):
        """The table must render for a ResNet trainer step: AMP O2
        strategy, ParallelTrainer, NHWC."""
        from paddle_tpu.vision.models.resnet import ResNet, BasicBlock
        from paddle_tpu.parallel import ParallelTrainer
        from paddle_tpu.distributed import fleet

        paddle.seed(0)
        net = ResNet(BasicBlock, 18, num_classes=10, data_format='NHWC')
        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=net.parameters())
        ce = nn.CrossEntropyLoss()
        strategy = fleet.DistributedStrategy()
        strategy.amp = True
        strategy.amp_configs['use_pure_fp16'] = True
        trainer = ParallelTrainer(net, opt, lambda out, y: ce(out, y),
                                  strategy=strategy)
        rs = np.random.RandomState(0)
        x = rs.randn(2, 32, 32, 3).astype('float32')
        y = rs.randint(0, 10, size=(2, 1)).astype('int64')
        rows = trainer.op_summary(x, y)
        out = capsys.readouterr().out
        assert 'op summary' in out
        assert rows, 'empty op table'
        opcodes = {r['opcode'] for r in rows}
        # a compiled conv net must show convolutions and/or fusions
        assert opcodes & {'convolution', 'fusion'}, opcodes
        # plumbing must not appear as work
        assert not opcodes & {'parameter', 'tuple', 'get-tuple-element'}
        # ranked by bytes, ratios normalized
        byte_counts = [r['bytes'] for r in rows]
        assert byte_counts == sorted(byte_counts, reverse=True)
        assert abs(sum(r['ratio'] for r in rows) - 1.0) < 1e-6
        # profiling must not advance the global RNG stream: a seeded
        # step after op_summary equals a seeded step without it
        from paddle_tpu.core import rng as rng_mod
        paddle.seed(7)
        k_after_summary = None
        trainer.op_summary(x, y, print_table=False)
        k_after_summary = np.asarray(rng_mod._state.key)
        paddle.seed(7)
        np.testing.assert_array_equal(np.asarray(rng_mod._state.key),
                                      k_after_summary)

    def test_sorted_by_calls_and_validation(self):
        def f(a, b):
            return jnp.tanh(a @ b).sum()

        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 4), jnp.float32)
        rows = profiler.op_summary(f, a, b, sorted_by='calls',
                                   print_table=False)
        calls = [r['calls'] for r in rows]
        assert calls == sorted(calls, reverse=True)
        with pytest.raises(ValueError):
            profiler.op_summary(f, a, b, sorted_by='flops')

    def test_top_truncation_lists_remainder(self, capsys):
        def f(a):
            for _ in range(3):
                a = jnp.sin(a) @ jnp.cos(a.T) + a
            return a.sum()

        rows = profiler.op_summary(f, jnp.ones((8, 8), jnp.float32),
                                   top=1)
        out = capsys.readouterr().out
        if len(rows) > 1:
            assert 'more)' in out
