"""fluid.dygraph 1.x class adapters + fluid.io/initializer/clip
long tail.

Reference analogue: /root/reference/python/paddle/fluid/dygraph/nn.py
(Conv3D, Conv2DTranspose, InstanceNorm, GroupNorm, SpectralNorm,
PRelu, BilinearTensorProduct, GRUUnit:1841, NCE:2019, Flatten) and
fluid/io.py / initializer.py / clip.py __all__; checked against the
per-op unittests (test_imperative_basic, test_gru_unit_op,
test_nce).
"""
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import dygraph as dg


def _t(a, dt='float32'):
    return paddle.to_tensor(np.asarray(a, dt))


class TestSurface:
    @pytest.mark.skipif(not os.path.isdir('/root/reference'),
                        reason='the reference checkout /root/reference '
                               'is not mounted')
    def test_all_four_namespaces_complete(self):
        for label, path, mod in (
            ('dygraph', 'dygraph/nn.py', fluid.dygraph),
            ('io', 'io.py', fluid.io),
            ('initializer', 'initializer.py', fluid.initializer),
            ('clip', 'clip.py', fluid.clip),
        ):
            src = open('/root/reference/python/paddle/fluid/'
                       + path).read()
            m = re.search(r"__all__ = \[(.*?)\]", src, re.S)
            for n in re.findall(r"'([a-zA-Z0-9_]+)'", m.group(1)):
                try:
                    assert hasattr(mod, n), f'{label}.{n}'
                except NotImplementedError:
                    pass


class TestDygraphAdapters:
    def test_conv_adapters_forward(self):
        paddle.seed(0)
        x3 = _t(np.random.RandomState(0).rand(1, 2, 4, 4, 4))
        out = dg.Conv3D(2, 3, 3, padding=1, act='relu')(x3)
        assert out.shape == [1, 3, 4, 4, 4]
        x2 = _t(np.random.RandomState(0).rand(1, 2, 4, 4))
        out = dg.Conv2DTranspose(2, 3, 2, stride=2)(x2)
        assert out.shape == [1, 3, 8, 8]
        out = dg.Conv3DTranspose(2, 3, 2, stride=2)(x3)
        assert out.shape == [1, 3, 8, 8, 8]

    def test_norm_adapters(self):
        paddle.seed(0)
        x = _t(np.random.RandomState(1).rand(2, 4, 3, 3))
        assert dg.InstanceNorm(4)(x).shape == [2, 4, 3, 3]
        assert dg.GroupNorm(4, 2)(x).shape == [2, 4, 3, 3]
        sn = dg.SpectralNorm([4, 6], dim=0, power_iters=2)
        w = _t(np.random.RandomState(2).rand(4, 6))
        assert sn(w).shape == [4, 6]

    def test_prelu_modes(self):
        paddle.seed(0)
        x = np.array([[-2.0, 4.0]], 'float32')
        out = np.asarray(dg.PRelu('all')(_t(x)).numpy())
        np.testing.assert_allclose(out, [[-0.5, 4.0]], rtol=1e-6)
        x4 = _t(np.random.RandomState(3).randn(1, 3, 2, 2))
        assert dg.PRelu('channel', channel=3)(x4).shape == \
            [1, 3, 2, 2]
        assert dg.PRelu('element',
                        input_shape=[1, 3, 2, 2])(x4).shape == \
            [1, 3, 2, 2]

    def test_bilinear_and_flatten(self):
        paddle.seed(0)
        a = _t(np.random.RandomState(4).rand(2, 3))
        b = _t(np.random.RandomState(5).rand(2, 4))
        out = dg.BilinearTensorProduct(3, 4, 5)(a, b)
        assert out.shape == [2, 5]
        f = dg.Flatten(start_axis=1, stop_axis=-1)
        assert f(_t(np.zeros((2, 3, 4)))).shape == [2, 12]
        f2 = dg.Flatten(start_axis=1, stop_axis=2)
        assert f2(_t(np.zeros((5, 2, 3, 4)))).shape == [5, 6, 4]

    def test_gru_unit_matches_manual(self):
        paddle.seed(0)
        D = 3
        g = dg.GRUUnit(3 * D)
        rs = np.random.RandomState(6)
        x = rs.randn(2, 3 * D).astype('float32')
        h = rs.randn(2, D).astype('float32')
        h2, rhp, gate = g(_t(x), _t(h))
        w = np.asarray(g.weight.value)
        b = np.asarray(g.bias.value)

        def sig(v):
            return 1 / (1 + np.exp(-v))
        u = sig(x[:, :D] + h @ w[:, :D] + b[:, :D])
        r = sig(x[:, D:2 * D] + h @ w[:, D:2 * D] + b[:, D:2 * D])
        c = np.tanh(x[:, 2 * D:] + (r * h) @ w[:, 2 * D:]
                    + b[:, 2 * D:])
        ref = (1 - u) * h + u * c
        np.testing.assert_allclose(np.asarray(h2.numpy()), ref,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rhp.numpy()), r * h,
                                   rtol=1e-4, atol=1e-5)

    def test_nce_trains(self):
        paddle.seed(0)
        nce = dg.NCE(num_total_classes=20, dim=8, num_neg_samples=5)
        rs = np.random.RandomState(7)
        x = _t(rs.randn(16, 8))
        y = _t(rs.randint(0, 20, (16, 1)), 'int64')
        opt = paddle.optimizer.SGD(0.1, parameters=nce.parameters())
        first = None
        for _ in range(12):
            loss = nce(x, y).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            v = float(np.asarray(loss.value))
            first = first if first is not None else v
        assert v < first

    def test_nce_custom_dist_raises(self):
        with pytest.raises(NotImplementedError):
            dg.NCE(10, 4, sampler='custom_dist')

    def test_tree_conv_non_goal(self):
        with pytest.raises(NotImplementedError, match='non-goal'):
            dg.TreeConv(1, 2, 3)


class TestFluidIo:
    @pytest.fixture(autouse=True)
    def _static_mode(self):
        # static mode must NOT leak into later tests (it flips
        # split()'s eager cache into per-call fresh weights and
        # fluid.dygraph.enabled() to False)
        paddle.enable_static()
        yield
        paddle.disable_static()

    def _prog(self):
        import paddle_tpu.static as static
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data('x', [None, 4], 'float32')
            y = fluid.layers.fc(x, 3)
            loss = fluid.layers.reduce_mean(y)
        return prog, loss

    def test_program_state_roundtrip(self, tmp_path):
        import paddle_tpu.static as static
        prog, _ = self._prog()
        exe = static.Executor()
        exe.run(static.default_startup_program())
        path = str(tmp_path / 'm')
        fluid.io.save(prog, path)
        state = fluid.io.load_program_state(path)
        assert state
        # mutate then restore
        p0 = prog.all_parameters()[0]
        import jax.numpy as jnp
        orig = np.asarray(p0.value).copy()
        p0.set_value(jnp.zeros_like(p0.value))
        fluid.io.set_program_state(prog, state)
        np.testing.assert_allclose(np.asarray(p0.value), orig)
        assert fluid.io.get_program_parameter(prog)
        assert fluid.io.get_program_persistable_vars(prog)

    def test_save_load_vars_subset(self, tmp_path):
        import paddle_tpu.static as static
        prog, _ = self._prog()
        exe = static.Executor()
        exe.run(static.default_startup_program())
        params = prog.all_parameters()
        d = str(tmp_path)
        fluid.io.save_vars(exe, d, main_program=prog,
                           vars=params[:1])
        import jax.numpy as jnp
        orig = np.asarray(params[0].value).copy()
        params[0].set_value(jnp.zeros_like(params[0].value))
        fluid.io.load_vars(exe, d, main_program=prog,
                           vars=params[:1])
        np.testing.assert_allclose(np.asarray(params[0].value), orig)

    def test_batch_alias(self):
        def reader():
            for i in range(5):
                yield [i]
        out = list(fluid.io.batch(reader, 2)())
        assert out[0] == [[0], [1]]


class TestInitializerAndClip:
    def test_numpy_array_initializer(self):
        from paddle_tpu.fluid.initializer import NumpyArrayInitializer
        init = NumpyArrayInitializer(np.array([1.0, 2.0], 'float32'))
        from paddle_tpu import nn
        lin = nn.Linear(
            1, 2, bias_attr=paddle.ParamAttr(initializer=init))
        np.testing.assert_allclose(np.asarray(lin.bias.value),
                                   [1.0, 2.0])

    def test_set_gradient_clip_warns_and_stores(self):
        import warnings
        from paddle_tpu.nn.clip import (set_gradient_clip,
                                        get_gradient_clip,
                                        ClipGradByNorm)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter('always')
            set_gradient_clip(ClipGradByNorm(1.0))
        assert any('deprecated' in str(x.message) for x in w)
        assert get_gradient_clip() is not None

    def test_error_clip_attr(self):
        from paddle_tpu.nn.clip import ErrorClipByValue
        c = ErrorClipByValue(max=2.0)
        assert c.max == 2.0 and c.min == -2.0


    def test_nce_noise_correction(self):
        # with the b = q*k correction, a uniform sampler with C=100,
        # k=5 shifts every logit by -log(5/100): check the loss of a
        # zero-logit model equals the closed form
        paddle.seed(0)
        from paddle_tpu import ParamAttr
        from paddle_tpu.nn.initializer import Constant
        nce = dg.NCE(num_total_classes=100, dim=4, num_neg_samples=5,
                     param_attr=ParamAttr(initializer=Constant(0.0)),
                     bias_attr=False, seed=3)
        x = _t(np.zeros((8, 4), 'float32'))
        y = _t(np.zeros((8, 1), 'int64'), 'int64')
        out = np.asarray(nce(x, y).numpy())
        import math
        b = 5.0 / 100.0
        z = -math.log(b)     # adjusted logit for every class
        pos = math.log(1 + math.exp(-z))
        neg = z + math.log(1 + math.exp(-z))
        np.testing.assert_allclose(out, np.full((8, 1),
                                                pos + 5 * neg),
                                   rtol=1e-5)

    def test_nce_sample_weight(self):
        paddle.seed(0)
        nce = dg.NCE(num_total_classes=20, dim=4, num_neg_samples=3,
                     seed=5)
        rs = np.random.RandomState(0)
        x = _t(rs.randn(4, 4))
        y = _t(rs.randint(0, 20, (4, 1)), 'int64')
        base = np.asarray(nce(x, y).numpy())
        w = _t(np.array([2.0, 1.0, 0.0, 1.0], 'float32'))
        weighted = np.asarray(nce(x, y, sample_weight=w).numpy())
        np.testing.assert_allclose(
            weighted.ravel(), base.ravel() * [2.0, 1.0, 0.0, 1.0],
            rtol=1e-5)
