"""Pallas kernels (SURVEY.md §2 item 36): flash attention, fused
LayerNorm, fused softmax — kernel logic validated in TPU-interpret mode
on the CPU suite; on-device parity is chip_smoke.py's kernels leg."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.flash_attention import (
    _flash, _reference as att_ref, flash_attention)
from paddle_tpu.ops.fused_norm import (
    _ln, _reference as ln_ref, fused_layer_norm)
from paddle_tpu.ops.fused_softmax import (
    _sm, _reference as sm_ref, fused_softmax)


@pytest.fixture()
def interp():
    with pltpu.force_tpu_interpret_mode():
        yield


def _rand(*shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


class TestFlashAttention:
    @pytest.mark.parametrize('causal', [False, True])
    def test_forward_matches_reference(self, interp, causal):
        q, k, v = (_rand(2, 256, 64, seed=i) for i in range(3))
        out = _flash(q, k, v, causal, 0.125, 128, 128)
        ref = att_ref(q, k, v, causal, 0.125)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_reference(self, interp):
        q, k, v = (_rand(1, 128, 64, seed=i + 5) for i in range(3))

        def lp(q, k, v):
            return jnp.sum(_flash(q, k, v, True, 0.125, 128, 128) ** 2)

        def lr(q, k, v):
            return jnp.sum(att_ref(q, k, v, True, 0.125) ** 2)

        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    def test_public_api_fallback_on_cpu(self):
        # no interpret scope: CPU backend → jnp reference path
        q, k, v = (_rand(2, 64, 32, seed=i) for i in range(3))
        out = flash_attention(q, k, v, causal=True)
        ref = att_ref(q, k, v, True, 1.0 / np.sqrt(32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6)


class TestFusedLayerNorm:
    def test_forward_matches_reference(self, interp):
        x = _rand(64, 128)
        g, b = _rand(128, seed=1), _rand(128, seed=2)
        y = _ln(x, g, b, 1e-5, 8)
        ref = ln_ref(x, g, b, 1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match_reference(self, interp):
        x = _rand(16, 128, seed=3)
        g, b = _rand(128, seed=4), _rand(128, seed=5)
        gp = jax.grad(lambda *a: jnp.sum(_ln(*a, 1e-5, 8) ** 2),
                      argnums=(0, 1, 2))(x, g, b)
        gr = jax.grad(lambda *a: jnp.sum(ln_ref(*a, 1e-5) ** 2),
                      argnums=(0, 1, 2))(x, g, b)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_public_api_fallback_on_cpu(self):
        x = _rand(5, 33)
        g, b = _rand(33, seed=1), _rand(33, seed=2)
        np.testing.assert_allclose(
            np.asarray(fused_layer_norm(x, g, b)),
            np.asarray(ln_ref(x, g, b, 1e-5)), rtol=1e-6)


class TestFusedSoftmax:
    def test_forward_matches_reference(self, interp):
        x = _rand(32, 256)
        y = _sm(x, None, 8)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(sm_ref(x, None)),
                                   rtol=1e-6, atol=1e-6)

    def test_masked(self, interp):
        x = _rand(16, 128)
        mask = jnp.where(_rand(16, 128, seed=9) > 0, 0.0, -1e9)
        y = _sm(x, mask, 8)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(sm_ref(x, mask)),
                                   rtol=1e-6, atol=1e-6)

    def test_grad(self, interp):
        x = _rand(8, 128, seed=11)
        gp = jax.grad(lambda x: jnp.sum(_sm(x, None, 8) ** 3))(x)
        gr = jax.grad(lambda x: jnp.sum(sm_ref(x, None) ** 3))(x)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-5, atol=1e-6)


class TestGPTModel:
    def test_gpt_tiny_eager_train_step(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import gpt_tiny
        paddle.seed(0)
        m = gpt_tiny()
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 128, (2, 16))
            .astype('int64'))
        logits = m(ids)
        assert list(logits.shape) == [2, 16, 128]
        loss = m.loss(logits, ids)
        loss.backward()
        g = m.gpt.blocks[0].attn.qkv.weight.grad
        assert g is not None
        assert np.isfinite(np.asarray(g.value)).all()

    def test_gpt_jit_loss_decreases(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import gpt_tiny
        from paddle_tpu.parallel import ParallelTrainer
        paddle.seed(0)
        m = gpt_tiny(num_layers=2, hidden_size=32, num_heads=2)
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        tr = ParallelTrainer(m, opt, lambda out, y: m.loss(out, y))
        ids = np.random.RandomState(0).randint(0, 128, (4, 16)) \
            .astype('int64')
        first = float(np.asarray(tr.step(ids, ids)))
        for _ in range(10):
            last = tr.step(ids, ids)
        assert float(np.asarray(last)) < first


class TestFlashAutotuneTable:
    """Per-shape block tuning table (a literal in the module;
    tools/tune_flash.py measures candidates on the chip): lookup and
    override semantics."""

    def test_default_when_untupled(self):
        import importlib
        fa = importlib.import_module('paddle_tpu.ops.flash_attention')
        assert fa._tuned_blocks(1024, 1024, 64, True) == \
            (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)

    def test_table_lookup_and_explicit_override(self, monkeypatch):
        import importlib
        fa = importlib.import_module('paddle_tpu.ops.flash_attention')
        monkeypatch.setattr(fa, '_tune_table',
                            {'2048,2048,128,1': (128, 256)})
        assert fa._tuned_blocks(2048, 2048, 128, True) == (128, 256)
        # other shapes still default
        assert fa._tuned_blocks(4096, 4096, 128, True) == \
            (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)

    def test_autotune_on_cpu_is_safe(self):
        """Without a TPU the pallas gate rejects every candidate and
        autotune returns the defaults without touching the table."""
        import importlib
        fa = importlib.import_module('paddle_tpu.ops.flash_attention')
        best, ms = fa.autotune_blocks(256, 256, 64, bh=1, iters=1)
        assert best == (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
