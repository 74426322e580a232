"""Fused linear+softmax+CE head (ops/fused_ce.py).

Reference analogue: softmax_with_cross_entropy fusion
(/root/reference/python/paddle/nn/functional/loss.py and
softmax_with_cross_entropy_op.cu) — the TPU version additionally
fuses the LM-head matmul so the [N, V] logits never materialize.
Numerics must match the unfused log_softmax path to f32 tolerance,
forward AND backward.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from jax import shard_map
from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy


def _ref_ce(x, w, labels):
    z = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    zl = jnp.take_along_axis(z, labels[:, None], axis=1)[:, 0]
    return lse - zl


class TestFusedCE:
    @pytest.mark.parametrize('V,chunks', [(64, 8), (50, 8), (37, 5),
                                          (64, 1)])
    def test_forward_matches_reference(self, V, chunks):
        rs = np.random.RandomState(0)
        N, H = 12, 16
        x = jnp.asarray(rs.randn(N, H).astype('float32'))
        w = jnp.asarray(rs.randn(H, V).astype('float32') * 0.1)
        y = jnp.asarray(rs.randint(0, V, N))
        got = fused_linear_cross_entropy(x, w, y, num_chunks=chunks)
        want = _ref_ce(x, w, y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_reference(self):
        rs = np.random.RandomState(1)
        N, H, V = 8, 12, 50
        x = jnp.asarray(rs.randn(N, H).astype('float32'))
        w = jnp.asarray(rs.randn(H, V).astype('float32') * 0.1)
        y = jnp.asarray(rs.randint(0, V, N))

        gx, gw = jax.grad(
            lambda a, b: fused_linear_cross_entropy(
                a, b, y, num_chunks=4).mean(), argnums=(0, 1))(x, w)
        rx, rw = jax.grad(
            lambda a, b: _ref_ce(a, b, y).mean(), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-4, atol=1e-5)

    def test_bf16_inputs_f32_accumulation(self):
        rs = np.random.RandomState(2)
        N, H, V = 8, 16, 32
        xf = rs.randn(N, H).astype('float32')
        wf = (rs.randn(H, V) * 0.1).astype('float32')
        y = jnp.asarray(rs.randint(0, V, N))
        got = fused_linear_cross_entropy(
            jnp.asarray(xf, jnp.bfloat16), jnp.asarray(wf, jnp.bfloat16),
            y, num_chunks=4)
        assert got.dtype == jnp.float32
        want = _ref_ce(jnp.asarray(xf, jnp.bfloat16).astype(jnp.float32),
                       jnp.asarray(wf, jnp.bfloat16).astype(jnp.float32),
                       y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)
        gx = jax.grad(lambda a: fused_linear_cross_entropy(
            a, jnp.asarray(wf, jnp.bfloat16), y,
            num_chunks=4).mean())(jnp.asarray(xf, jnp.bfloat16))
        assert gx.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(gx, np.float32)).all()

    def test_jit_compiles(self):
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(4, 8).astype('float32'))
        w = jnp.asarray(rs.randn(8, 20).astype('float32'))
        y = jnp.asarray(rs.randint(0, 20, 4))
        f = jax.jit(lambda a, b, c: fused_linear_cross_entropy(
            a, b, c, num_chunks=4).mean())
        assert np.isfinite(float(f(x, w, y)))


class TestGPTFusedHead:
    def test_loss_and_grads_match_unfused(self):
        from paddle_tpu.models.gpt import gpt_tiny
        paddle.seed(0)
        model = gpt_tiny(fused_head=True, fused_head_chunks=4)
        model.train()
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rs.randint(0, 128, size=(2, 16)).astype('int64'))

        loss_f = model.loss(model(ids), ids)
        loss_f.backward()
        gf = np.asarray(model.gpt.wte.weight.grad.value).copy()
        lf = float(np.asarray(loss_f.value))
        model.clear_gradients() if hasattr(model, 'clear_gradients') \
            else [p.clear_grad() for p in model.parameters()
                  if p.grad is not None]

        model.config.fused_head = False
        loss_u = model.loss(model(ids), ids)
        loss_u.backward()
        gu = np.asarray(model.gpt.wte.weight.grad.value)
        lu = float(np.asarray(loss_u.value))

        np.testing.assert_allclose(lf, lu, rtol=1e-5)
        np.testing.assert_allclose(gf, gu, rtol=1e-4, atol=1e-6)

    def test_eval_still_returns_logits(self):
        from paddle_tpu.models.gpt import gpt_tiny
        paddle.seed(0)
        model = gpt_tiny(fused_head=True)
        model.eval()
        ids = paddle.to_tensor(np.ones((1, 8), 'int64'))
        out = model(ids)
        assert out.shape[-1] == model.config.vocab_size

    def test_trainer_step_with_fused_head(self):
        from paddle_tpu.models.gpt import gpt_tiny
        from paddle_tpu.parallel import ParallelTrainer
        from paddle_tpu.distributed import fleet, env as dist_env
        paddle.seed(0)
        model = gpt_tiny(fused_head=True, fused_head_chunks=4)
        opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                     parameters=model.parameters())
        strategy = fleet.DistributedStrategy()
        fleet.init(is_collective=True, strategy=strategy)
        try:
            trainer = ParallelTrainer(
                model, opt, lambda out, y: model.loss(out, y),
                strategy=strategy)
            rs = np.random.RandomState(0)
            ids = rs.randint(0, 128, size=(8, 16)).astype('int64')
            l1 = float(np.asarray(trainer.step(ids, ids)))
            l2 = float(np.asarray(trainer.step(ids, ids)))
            assert np.isfinite(l1) and np.isfinite(l2)
            assert l2 < l1   # it actually optimizes through the head
        finally:
            dist_env.set_mesh(None)


class TestBertFusedHead:
    def test_mlm_loss_and_grads_match_unfused(self):
        from paddle_tpu.models.bert import bert_tiny
        paddle.seed(0)
        model = bert_tiny(fused_head=True, fused_head_chunks=4)
        model.train()
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rs.randint(0, 128, size=(2, 16)).astype('int64'))
        labels = rs.randint(0, 128, size=(2, 16)).astype('int64')
        labels[rs.rand(2, 16) > 0.3] = -100    # MLM ignore mask
        lb = paddle.to_tensor(labels)

        loss_f = model.loss(model(ids), lb)
        loss_f.backward()
        gf = np.asarray(
            model.bert.word_emb.weight.grad.value).copy()
        lf = float(np.asarray(loss_f.value))
        for p in model.parameters():
            if p.grad is not None:
                p.clear_grad()

        model.config.fused_head = False
        loss_u = model.loss(model(ids), lb)
        loss_u.backward()
        gu = np.asarray(model.bert.word_emb.weight.grad.value)
        lu = float(np.asarray(loss_u.value))

        np.testing.assert_allclose(lf, lu, rtol=1e-5)
        np.testing.assert_allclose(gf, gu, rtol=1e-4, atol=1e-6)

    def test_all_ignored_is_finite(self):
        from paddle_tpu.models.bert import bert_tiny
        paddle.seed(0)
        model = bert_tiny(fused_head=True, fused_head_chunks=4)
        model.train()
        ids = paddle.to_tensor(np.ones((1, 8), 'int64'))
        lb = paddle.to_tensor(np.full((1, 8), -100, 'int64'))
        loss = model.loss(model(ids), lb)
        assert np.isfinite(float(np.asarray(loss.value)))

    def test_eval_returns_logits(self):
        from paddle_tpu.models.bert import bert_tiny
        paddle.seed(0)
        model = bert_tiny(fused_head=True)
        model.eval()
        ids = paddle.to_tensor(np.ones((1, 8), 'int64'))
        logits, nsp = model(ids)
        assert logits.shape[-1] == model.config.vocab_size

    def test_train_forward_eval_loss_toggle_stays_fused(self):
        # loss() keys off the produced SHAPE, not self.training: a
        # train-forward followed by eval-mode loss must not feed
        # hidden states into the unfused CE branch
        from paddle_tpu.models.bert import bert_tiny
        paddle.seed(0)
        model = bert_tiny(fused_head=True, fused_head_chunks=4)
        model.train()
        ids = paddle.to_tensor(np.ones((1, 8), 'int64'))
        out = model(ids)
        model.eval()
        lb = paddle.to_tensor(np.zeros((1, 8), 'int64'))
        loss = model.loss(out, lb)
        assert np.isfinite(float(np.asarray(loss.value)))


class TestTpFusedCE:
    def _harness(self, V, H, N, tp, chunks, dtype='float32',
                 labels=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.ops.fused_ce import \
            fused_linear_cross_entropy_tp
        rs = np.random.RandomState(0)
        x = rs.randn(N, H).astype(dtype)
        w = (rs.randn(H, V) * 0.1).astype(dtype)
        y = np.asarray(labels) if labels is not None \
            else rs.randint(0, V, N)
        mesh = Mesh(np.asarray(jax.devices()[:tp]), ('tp',))

        def step(xv, wv, yv):
            return fused_linear_cross_entropy_tp(
                xv, wv, yv, axis='tp', num_chunks=chunks)

        f = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(None, 'tp'), P()), out_specs=P()))
        got = np.asarray(f(jnp.asarray(x), jnp.asarray(w),
                           jnp.asarray(y)))
        want = np.asarray(_ref_ce(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(w, jnp.float32),
                                  jnp.asarray(y)))
        return got, want, (x, w, y, mesh, step)

    @pytest.mark.parametrize('V,chunks', [(64, 4), (56, 3)])
    def test_forward_matches_unsharded(self, V, chunks):
        got, want, _ = self._harness(V, 16, 8, tp=4, chunks=chunks)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_shard_boundary_labels(self):
        # every shard's FIRST and LAST global id — with ragged chunks
        # (Vs=14, Vc=5) these land in pad cells of the neighbouring
        # shard's chunk grid and must neither gather -inf nor leak
        V, tp = 56, 4
        Vs = V // tp
        labels = []
        for r in range(tp):
            labels += [r * Vs, r * Vs + Vs - 1]
        got, want, _ = self._harness(V, 16, len(labels), tp=tp,
                                     chunks=3, labels=labels)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_boundary_label_gradients(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.ops.fused_ce import \
            fused_linear_cross_entropy_tp
        V, tp, H = 56, 4, 12
        Vs = V // tp
        labels = np.array([0, 13, 14, 27, 28, 41, 42, 55])
        rs = np.random.RandomState(1)
        x = rs.randn(8, H).astype('float32')
        w = (rs.randn(H, V) * 0.1).astype('float32')
        mesh = Mesh(np.asarray(jax.devices()[:tp]), ('tp',))

        def loss_sharded(xv, wv):
            return jnp.mean(fused_linear_cross_entropy_tp(
                xv, wv, jnp.asarray(labels), num_chunks=3))

        g = jax.jit(shard_map(
            jax.grad(loss_sharded, argnums=(0, 1)), mesh=mesh,
            in_specs=(P(), P(None, 'tp')),
            out_specs=(P(), P(None, 'tp'))))
        gx, gw = g(jnp.asarray(x), jnp.asarray(w))
        rx, rw = jax.grad(
            lambda a, b: jnp.mean(_ref_ce(a, b,
                                          jnp.asarray(labels))),
            argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-4, atol=1e-5)

    def test_gradients_match_unsharded(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        got, want, (x, w, y, mesh, step) = self._harness(
            64, 12, 8, tp=4, chunks=4)

        def loss_sharded(xv, wv):
            return jnp.mean(step(xv, wv, jnp.asarray(y)))

        g = jax.jit(shard_map(
            jax.grad(loss_sharded, argnums=(0, 1)), mesh=mesh,
            in_specs=(P(), P(None, 'tp')),
            out_specs=(P(), P(None, 'tp'))))
        gx, gw = g(jnp.asarray(x), jnp.asarray(w))
        rx, rw = jax.grad(
            lambda a, b: jnp.mean(_ref_ce(a, b, jnp.asarray(y))),
            argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-4, atol=1e-5)


class TestDenseCEBackward:
    """F.cross_entropy's hard-label path carries a custom_vjp whose
    backward is dense (softmax - one_hot) math instead of the autodiff
    scatter-add (serialized on TPU)."""

    def test_grad_matches_autodiff_gather(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(48, 53).astype('float32'))
        lab = jnp.asarray(rs.randint(0, 53, size=(48,)), jnp.int32)
        lab = lab.at[::5].set(-100)   # exercise ignore_index masking

        def autodiff(xv):
            logp = jax.nn.log_softmax(xv, -1)
            mask = lab != -100
            safe = jnp.where(mask, lab, 0)
            per = -jnp.take_along_axis(logp, safe[:, None], -1)[:, 0]
            per = jnp.where(mask, per, 0.0)
            return per.sum() / mask.sum()

        def ours(xv):
            return F.cross_entropy(paddle.Tensor(xv),
                                   paddle.Tensor(lab)).value

        g_ref = jax.grad(autodiff)(x)
        g_got = jax.grad(ours)(x)
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-6)

    def test_bf16_dtype_and_jaxpr_has_no_scatter(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(16, 33), jnp.bfloat16)
        lab = jnp.asarray(rs.randint(0, 33, size=(16,)), jnp.int32)

        def ours(xv):
            return F.cross_entropy(
                paddle.Tensor(xv),
                paddle.Tensor(lab)).value.astype(jnp.float32)

        g = jax.grad(ours)(x)
        assert g.dtype == jnp.bfloat16
        jaxpr = str(jax.make_jaxpr(jax.grad(ours))(x))
        assert 'scatter' not in jaxpr

    def test_nll_loss_dense_backward(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(5)
        logp = jax.nn.log_softmax(
            jnp.asarray(rs.randn(24, 19), jnp.float32), -1)
        lab = jnp.asarray(rs.randint(0, 19, size=(24,)), jnp.int32)
        lab = lab.at[::6].set(-100)

        def ours(lp):
            return F.nll_loss(paddle.Tensor(lp), paddle.Tensor(lab)).value

        def ref(lp):
            m = lab != -100
            s = jnp.where(m, lab, 0)
            p = -jnp.take_along_axis(lp, s[:, None], -1)[:, 0] * m
            return p.sum() / m.sum()

        np.testing.assert_allclose(np.asarray(jax.grad(ours)(logp)),
                                   np.asarray(jax.grad(ref)(logp)),
                                   rtol=1e-6, atol=1e-7)
        assert 'scatter' not in str(jax.make_jaxpr(jax.grad(ours))(logp))

    def test_nll_loss_rank4_classes_axis1(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(6)
        lp = jax.nn.log_softmax(
            jnp.asarray(rs.randn(4, 6, 5, 3), jnp.float32), 1)
        lab = jnp.asarray(rs.randint(0, 6, size=(4, 5, 3)), jnp.int32)
        got = F.nll_loss(paddle.Tensor(lp), paddle.Tensor(lab)).numpy()
        lpn, labn = np.asarray(lp), np.asarray(lab)
        want = -np.mean([lpn[n, labn[n, i, j], i, j]
                         for n in range(4) for i in range(5)
                         for j in range(3)])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
