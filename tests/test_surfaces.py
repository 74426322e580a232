"""API-surface parity: static.nn, hub, inference, onnx, incubate,
LocalSGD (SURVEY.md §2 items 3, 33, 40 + aux surfaces)."""
import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import nn, static
from paddle_tpu.distributed import env as dist_env
import paddle_tpu.distributed as dist


@pytest.fixture(autouse=True)
def clean_mesh():
    yield
    dist_env.set_mesh(None)


class TestStaticNN:
    def test_fc_conv_bn_program(self):
        paddle.enable_static()
        try:
            prog = static.Program()
            with static.program_guard(prog):
                img = static.data('img', [None, 1, 8, 8])
                h = static.nn.conv2d(img, 4, 3, padding=1, act='relu')
                h = static.nn.batch_norm(h)
                out = static.nn.fc(h, 10)
            exe = static.Executor()
            res = exe.run(prog,
                          feed={'img': np.random.randn(2, 1, 8, 8)
                                .astype('float32')},
                          fetch_list=[out])
            assert res[0].shape == (2, 10)
        finally:
            paddle.disable_static()

    def test_embedding_dropout_layernorm(self):
        paddle.enable_static()
        try:
            prog = static.Program()
            with static.program_guard(prog):
                ids = static.data('ids', [None, 5], dtype='int64')
                e = static.nn.embedding(ids, size=[20, 8])
                e = static.nn.layer_norm(e, begin_norm_axis=2)
                e = static.nn.dropout(e, 0.5, is_test=True)
            exe = static.Executor()
            res = exe.run(prog,
                          feed={'ids': np.random.randint(
                              0, 20, (3, 5)).astype('int64')},
                          fetch_list=[e])
            assert res[0].shape == (3, 5, 8)
        finally:
            paddle.disable_static()


class TestHub:
    def test_local_hub_roundtrip(self, tmp_path):
        (tmp_path / 'hubconf.py').write_text(
            "import paddle_tpu\n"
            "def tiny_mlp(width=4):\n"
            "    '''A tiny MLP.'''\n"
            "    from paddle_tpu import nn\n"
            "    return nn.Sequential(nn.Linear(2, width),\n"
            "                         nn.Linear(width, 1))\n")
        names = paddle.hub.list(str(tmp_path))
        assert 'tiny_mlp' in names
        assert 'tiny MLP' in paddle.hub.help(str(tmp_path), 'tiny_mlp')
        m = paddle.hub.load(str(tmp_path), 'tiny_mlp', width=8)
        out = m(paddle.to_tensor(np.zeros((1, 2), 'float32')))
        assert list(out.shape) == [1, 1]

    def test_remote_source_rejected(self):
        with pytest.raises(RuntimeError, match='egress'):
            paddle.hub.load('user/repo', 'model', source='github')


class TestInferenceAndOnnx:
    def test_predictor_roundtrip(self, tmp_path):
        net = nn.Sequential(nn.Linear(4, 3), nn.Tanh())
        net.eval()
        path = str(tmp_path / 'deploy')
        from paddle_tpu.static import InputSpec
        paddle.jit.save(net, path,
                        input_spec=[InputSpec([1, 4], 'float32')])
        config = paddle.inference.Config(path)
        pred = paddle.inference.create_predictor(config)
        x = np.random.randn(1, 4).astype('float32')
        h = pred.get_input_handle(pred.get_input_names()[0])
        h.copy_from_cpu(x)
        assert pred.run()
        out = pred.get_output_handle(
            pred.get_output_names()[0]).copy_to_cpu()
        ref = np.asarray(net(paddle.to_tensor(x)).value)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_onnx_export_raises_with_pointer(self):
        with pytest.raises(NotImplementedError, match='StableHLO'):
            paddle.onnx.export(nn.Linear(2, 2), '/tmp/x')

    def test_incubate_exports(self):
        assert callable(paddle.incubate.flash_attention)
        assert callable(paddle.incubate.ring_attention_spmd)
        assert callable(paddle.incubate.gpipe_spmd)


class TestLocalSGD:
    def test_converges_and_syncs(self):
        from paddle_tpu.parallel import LocalSGDTrainer
        dist.init_parallel_env(axes={'dp': 8})
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 1))
        opt = paddle.optimizer.Momentum(0.1,
                                        parameters=net.parameters())
        tr = LocalSGDTrainer(net, opt,
                             lambda o, y: ((o - y) ** 2).mean(),
                             k_steps=4)
        rs = np.random.RandomState(1)
        X = rs.randn(32, 8).astype('float32')
        Y = (X.sum(1, keepdims=True) > 0).astype('float32')
        losses = [float(np.asarray(tr.step(X, Y))) for _ in range(24)]
        assert losses[-1] < losses[0] * 0.5
        tr.sync_to_model()
        # after sync all replicas agree: stacked rows identical
        w = np.asarray(jax.tree_util.tree_leaves(tr.params)[0])
        np.testing.assert_allclose(w[0], w[-1], rtol=1e-6)

    def test_replicas_diverge_between_syncs(self):
        from paddle_tpu.parallel import LocalSGDTrainer
        dist.init_parallel_env(axes={'dp': 8})
        paddle.seed(0)
        net = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(0.5, parameters=net.parameters())
        tr = LocalSGDTrainer(net, opt,
                             lambda o, y: ((o - y) ** 2).mean(),
                             k_steps=1000)  # never auto-sync
        rs = np.random.RandomState(2)
        X = rs.randn(32, 4).astype('float32')
        Y = rs.randn(32, 1).astype('float32')
        tr.step(X, Y)
        w = np.asarray(jax.tree_util.tree_leaves(tr.params)[0])
        # different batch shards → different local params
        assert np.abs(w[0] - w[-1]).max() > 1e-6
        tr.sync()
        w = np.asarray(jax.tree_util.tree_leaves(tr.params)[0])
        np.testing.assert_allclose(w[0], w[-1], rtol=1e-6)


class TestPredictorNamedInputs:
    def test_real_spec_names_surface(self, tmp_path):
        """Saved InputSpec.name travels into Predictor.get_input_names
        (reference deployments feed tensors by their real names)."""
        net = nn.Sequential(nn.Linear(4, 3), nn.Tanh())
        net.eval()
        path = str(tmp_path / 'named')
        from paddle_tpu.static import InputSpec
        paddle.jit.save(net, path,
                        input_spec=[InputSpec([1, 4], 'float32',
                                              name='pixel_values')])
        pred = paddle.inference.create_predictor(
            paddle.inference.Config(path))
        assert pred.get_input_names() == ['pixel_values']
        h = pred.get_input_handle('pixel_values')
        x = np.random.randn(1, 4).astype('float32')
        h.copy_from_cpu(x)
        assert pred.run()
        out = pred.get_output_handle(
            pred.get_output_names()[0]).copy_to_cpu()
        ref = np.asarray(net(paddle.to_tensor(x)).value)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_duplicate_spec_names_rejected(self, tmp_path):
        from paddle_tpu.static import InputSpec
        net = nn.Linear(4, 3)
        with pytest.raises(ValueError, match='duplicate'):
            paddle.jit.save(net, str(tmp_path / 'd'), input_spec=[
                InputSpec([1, 4], 'float32', name='x'),
                InputSpec([1, 4], 'float32', name='x')])

    def test_unfed_input_raises_clearly(self, tmp_path):
        from paddle_tpu.static import InputSpec
        net = nn.Linear(4, 3)
        net.eval()
        path = str(tmp_path / 'u')
        paddle.jit.save(net, path, input_spec=[
            InputSpec([1, 4], 'float32', name='pixel_values')])
        pred = paddle.inference.create_predictor(
            paddle.inference.Config(path))
        with pytest.raises(KeyError, match='pixel_values'):
            pred.run()


class TestCompatDeviceNamespaces:
    """paddle.compat / paddle.device / paddle.callbacks namespaces
    (reference python/paddle/compat.py, device.py, callbacks.py)."""

    def test_compat(self):
        import paddle_tpu as paddle
        assert paddle.compat.to_text(b'ab') == 'ab'
        assert paddle.compat.to_text([b'a', 'b']) == ['a', 'b']
        assert paddle.compat.to_bytes('ab') == b'ab'
        d = {'k': b'v'}
        paddle.compat.to_text(d, inplace=True)
        assert d == {'k': 'v'}
        # py2-style half-away-from-zero rounding
        assert paddle.compat.round(2.5) == 3.0
        assert paddle.compat.round(-2.5) == -3.0
        assert paddle.compat.floor_division(7, 2) == 3
        assert 'boom' in paddle.compat.get_exception_message(
            ValueError('boom'))

    def test_device_namespace(self):
        import paddle_tpu as paddle
        dev = paddle.device.get_device()
        assert isinstance(dev, str) and dev
        assert paddle.device.is_compiled_with_cuda() is False
        assert paddle.device.is_compiled_with_xpu() is False
        assert paddle.device.get_cudnn_version() is None

    def test_callbacks_namespace(self):
        import paddle_tpu as paddle
        assert hasattr(paddle.callbacks, 'EarlyStopping')
        assert hasattr(paddle.callbacks, 'ModelCheckpoint')


class TestUtilsNamespace:
    """paddle.utils additions: unique_name / cpp_extension / download
    (reference utils/ package)."""

    def test_unique_name(self):
        from paddle_tpu.utils import unique_name
        with unique_name.guard():
            a = unique_name.generate('fc')
            b = unique_name.generate('fc')
            c = unique_name.generate('conv')
        assert (a, b, c) == ('fc_0', 'fc_1', 'conv_0')
        with unique_name.guard('pre'):
            assert unique_name.generate('fc') == 'pre_fc_0'
        # guard restored the outer generator's counters
        with unique_name.guard():
            assert unique_name.generate('fc') == 'fc_0'

    def test_cpp_extension_load(self, tmp_path):
        import shutil
        import pytest as _pytest
        if shutil.which('g++') is None:
            _pytest.skip('no g++')
        from paddle_tpu.utils import cpp_extension
        src = tmp_path / 'ext.cc'
        src.write_text(
            'extern "C" int add3(int a) { return a + 3; }\n')
        lib = cpp_extension.load('t_ext', [str(src)],
                                 build_directory=str(tmp_path))
        assert lib.add3(4) == 7
        with _pytest.raises(RuntimeError):
            cpp_extension.CUDAExtension(['x.cu'])

    def test_download_cache_miss_raises(self):
        import pytest as _pytest
        from paddle_tpu.utils import download
        with _pytest.raises(RuntimeError, match='no .*egress|not in'):
            download.get_weights_path_from_url(
                'https://example.com/definitely_not_cached_weights.pdparams')

    def test_run_check(self, capsys):
        import paddle_tpu as paddle
        paddle.utils.run_check()
        assert 'successfully' in capsys.readouterr().out
