"""Pallas kernels (SURVEY.md §2 item 36): flash attention, fused
LayerNorm, fused softmax — kernel logic validated in TPU-interpret mode
on the CPU suite; on-device parity is chip_smoke.py's kernels leg."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.flash_attention import (
    _flash, _kv_index_map, _q_index_map, _reference as att_ref,
    flash_attention)
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


def _fwd_bwd(attn, q, k, v, w):
    """(out, dq, dk, dv) of sum(attn(q, k, v) * w), as float32 arrays."""
    def loss(q, k, v):
        o = attn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * w), o
    (_, o), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return [np.asarray(x, np.float32) for x in (o,) + grads]


def _kernel_eqns(jaxpr, found):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (a pallas_call's kernel, a pl.when's branches)."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    _kernel_eqns(sub, found)
    return found


class TestFlashOperandDtype:
    """The kernels hand the MXU the dtype their operands are stored
    in.  A kernel has no counter, so the jaxpr is the proof."""

    # Largest error over the largest reference value.  Read from the
    # kernel as it was before PR 32 (every operand cast to float32) on
    # these very inputs: 1.6e-3 to 3.3e-3 over the eighteen
    # (causal, blocks, tensor) cases, which is the output's one bf16
    # rounding (2^-9).  With p and ds rounded to bf16 before their
    # matmuls the kernel reads 1.7e-3 to 3.9e-3 (CPU, interpret mode;
    # on the chip the two kernels agree bit for bit, PERF.md section
    # 6, PR 32); 2^-7 leaves a second bf16 rounding room.
    BF16_TOL = 2.0 ** -7

    @pytest.mark.parametrize('blocks', [(128, 128), (128, 256)])
    @pytest.mark.parametrize('causal', [False, True, 'strict'])
    def test_bf16_operands_match_f32_reference(self, interp, causal,
                                               blocks):
        # T=512: a [4 x 4] and a [4 x 2] grid, so block_q != block_k,
        # tiles on, below and above the diagonal all occur
        rs = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rs.randn(2, 512, 64), jnp.bfloat16)
                   for _ in range(3))
        w = jnp.asarray(rs.randn(2, 512, 64), jnp.float32)
        got = _fwd_bwd(lambda q, k, v: _flash(q, k, v, causal, 0.125,
                                              *blocks), q, k, v, w)
        want = _fwd_bwd(lambda q, k, v: att_ref(q, k, v, causal, 0.125),
                        *(x.astype(jnp.float32) for x in (q, k, v)), w)
        for name, g, r in zip(('out', 'dq', 'dk', 'dv'), got, want):
            err = np.abs(g - r).max() / np.abs(r).max()
            assert err <= self.BF16_TOL, (name, err)

    @pytest.mark.parametrize('kernel', ['flash_fwd', 'flash_bwd_dq',
                                        'flash_bwd_dkv'])
    @pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
    def test_matmuls_take_the_stored_dtype(self, kernel, dtype):
        x = jnp.zeros((1, 256, 64), dtype)
        traced = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(_flash(q, k, v, True, 0.125, 128,
                                           128).astype(jnp.float32)),
            argnums=(0, 1, 2)))(x, x, x)
        calls = [e for e in _kernel_eqns(traced.jaxpr, [])
                 if e.primitive.name == 'pallas_call'
                 and e.params['name'] == kernel]
        assert len(calls) == 1
        body = _kernel_eqns(calls[0].params['jaxpr'], [])
        dots = [e for e in body if e.primitive.name == 'dot_general']
        assert len(dots) == {'flash_fwd': 2, 'flash_bwd_dq': 3,
                             'flash_bwd_dkv': 4}[kernel]
        # float32 operands take the caller's matmul precision (this
        # suite's conftest sets 'highest'), as at the parent; Mosaic
        # refuses it on bfloat16 ones, whose product is exact anyway
        want = jax.lax.Precision.HIGHEST if dtype == 'float32' \
            else jax.lax.Precision.DEFAULT
        for e in dots:
            assert [str(a.aval.dtype) for a in e.invars] == [dtype] * 2
            assert str(e.outvars[0].aval.dtype) == 'float32'
            assert e.params['precision'] == (want, want)
        if dtype == 'float32':
            # a float32 caller gets the matmuls the parent gave it:
            # nothing in the kernel is bfloat16
            seen = {str(a.aval.dtype) for e in body
                    for a in list(e.invars) + list(e.outvars)
                    if hasattr(a.aval, 'dtype')}
            assert 'bfloat16' not in seen


class TestFlashIndexMaps:
    """A causal grid step above the diagonal names the block its
    neighbour holds, so the pipeline fetches nothing for it."""
    BQ, BK, NQ, NK = 128, 256, 8, 4          # T=1024: an [8 x 4] grid

    def _computes(self, qi, ki):
        return ki * self.BK <= qi * self.BQ + self.BQ - 1

    @pytest.mark.parametrize('causal', [True, 'strict'])
    def test_kv_map_stays_on_the_rows_last_block(self, causal):
        kv_map = _kv_index_map(causal, self.BQ, self.BK)
        for qi in range(self.NQ):
            last = max(ki for ki in range(self.NK)
                       if self._computes(qi, ki))
            for ki in range(self.NK):
                b, blk, z = kv_map(3, qi, ki)
                want = ki if self._computes(qi, ki) else last
                assert (b, int(blk), z) == (3, want, 0), (qi, ki)

    @pytest.mark.parametrize('causal', [True, 'strict'])
    def test_q_map_stays_on_the_columns_first_block(self, causal):
        q_map = _q_index_map(causal, self.BQ, self.BK, self.NQ)
        for ki in range(self.NK):
            first = min(qi for qi in range(self.NQ)
                        if self._computes(qi, ki))
            for qi in range(self.NQ):
                b, blk, z = q_map(3, ki, qi)
                want = qi if self._computes(qi, ki) else first
                assert (b, int(blk), z) == (3, want, 0), (qi, ki)

    def test_non_causal_maps_name_every_block(self):
        kv_map = _kv_index_map(False, self.BQ, self.BK)
        q_map = _q_index_map(False, self.BQ, self.BK, self.NQ)
        for qi in range(self.NQ):
            for ki in range(self.NK):
                assert kv_map(0, qi, ki) == (0, ki, 0)
                assert q_map(0, ki, qi) == (0, qi, 0)

    def test_more_keys_than_queries_stays_in_range(self):
        # tk > tq (a ring step's partial): key columns past the last
        # query row compute nothing and must still name a real block
        q_map = _q_index_map(True, 128, 128, 2)
        assert [int(q_map(0, ki, 0)[1]) for ki in range(4)] == \
            [0, 1, 1, 1]


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

    def test_the_table_holds_the_one_swept_shape(self):
        """PR 32's sweep at the benchmark's shape is the only entry:
        no other shape has a cell that could have measured one."""
        import importlib
        fa = importlib.import_module('paddle_tpu.ops.flash_attention')
        assert fa._tune_table == {'2048,2048,128,1': (512, 1024)}
        assert fa._tuned_blocks(2048, 2048, 128, True) == (512, 1024)
        assert fa.shapes_tile(2048, 2048, 128, 512, 1024)

    def test_autotune_on_cpu_is_safe(self):
        """Without a TPU the pallas gate rejects every candidate and
        autotune returns the defaults without touching the table."""
        import importlib
        fa = importlib.import_module('paddle_tpu.ops.flash_attention')
        best, ms = fa.autotune_blocks(256, 256, 64, bh=1, iters=1)
        assert best == (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
