"""fluid.layers 1.x long-tail compat (fluid/layers_compat.py).

Reference analogue: the per-op unittests under
/root/reference/python/paddle/fluid/tests/unittests/ (test_pad_op,
test_mean_iou, test_smooth_l1_loss_op, test_space_to_depth_op,
test_temporal_shift_op, test_linear_chain_crf_op, test_crf_decoding,
test_ctc_align, test_psroi_pool_op, ...).  Full-surface resolution is
asserted against the reference __all__ lists.
"""
import math
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid

L = None


def setup_module():
    global L
    L = fluid.layers


def _t(a, dt='float32'):
    return paddle.to_tensor(np.asarray(a, dt))


class TestSurfaceComplete:
    @pytest.mark.skipif(not os.path.isdir('/root/reference'),
                        reason='the reference checkout /root/reference '
                               'is not mounted')
    def test_reference_all_lists_resolve(self):
        total = missing = 0
        for mod in ('nn', 'tensor', 'control_flow', 'sequence_lod'):
            src = open('/root/reference/python/paddle/fluid/layers/'
                       f'{mod}.py').read()
            m = re.search(r"__all__ = \[(.*?)\]", src, re.S)
            for n in re.findall(r"'([a-zA-Z0-9_]+)'", m.group(1)):
                total += 1
                try:
                    ok = hasattr(L, n)
                except NotImplementedError:
                    ok = True   # documented non-goal still resolves
                if not ok:
                    missing += 1
        assert missing == 0, f'{missing}/{total} names missing'

    def test_non_goals_raise_with_pointer(self):
        for n in ('DynamicRNN', 'While', 'lod_reset', 'im2sequence'):
            with pytest.raises(NotImplementedError, match='non-goal'):
                getattr(L, n)


class TestSimpleOps:
    def test_activations(self):
        x = np.array([[-1.0, 0.5, 2.0]], 'float32')
        np.testing.assert_allclose(
            np.asarray(L.brelu(_t(x), 0.0, 1.0).numpy()),
            np.clip(x, 0, 1))
        np.testing.assert_allclose(
            np.asarray(L.selu(_t(x)).numpy()),
            1.0507009873554805 * np.where(
                x > 0, x, 1.6732632423543772 * (np.exp(x) - 1)),
            rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(L.swish(_t(x)).numpy()),
            x / (1 + np.exp(-x)), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(L.soft_relu(_t(x), 40.0).numpy()),
            np.log1p(np.exp(x)), rtol=1e-5)

    def test_scale_and_mul(self):
        x = np.array([[1.0, 2.0]], 'float32')
        np.testing.assert_allclose(
            np.asarray(L.scale(_t(x), scale=2.0, bias=1.0).numpy()),
            x * 2 + 1)
        np.testing.assert_allclose(
            np.asarray(L.scale(_t(x), scale=2.0, bias=1.0,
                               bias_after_scale=False).numpy()),
            (x + 1) * 2)
        a = np.arange(6, dtype='float32').reshape(2, 3)
        b = np.arange(12, dtype='float32').reshape(3, 4)
        np.testing.assert_allclose(
            np.asarray(L.mul(_t(a), _t(b)).numpy()), a @ b)

    def test_pad_family(self):
        x = np.ones((1, 1, 2, 2), 'float32')
        out = np.asarray(L.pad(_t(x), [0, 0, 0, 0, 1, 1, 1, 1],
                               5.0).numpy())
        assert out.shape == (1, 1, 4, 4)
        assert out[0, 0, 0, 0] == 5.0
        out2 = np.asarray(L.pad2d(_t(x), [1, 0, 0, 1]).numpy())
        assert out2.shape == (1, 1, 3, 3)
        y = np.ones((1, 1, 1, 1), 'float32')
        out3 = np.asarray(
            L.pad_constant_like(_t(x), _t(y), 7.0).numpy())
        assert out3.shape == x.shape and out3[0, 0, 1, 1] == 7.0

    def test_space_to_depth_and_shuffle(self):
        x = np.arange(16, dtype='float32').reshape(1, 1, 4, 4)
        out = np.asarray(L.space_to_depth(_t(x), 2).numpy())
        assert out.shape == (1, 4, 2, 2)
        np.testing.assert_allclose(out[0, 0], [[0, 2], [8, 10]])
        c = np.arange(8, dtype='float32').reshape(1, 4, 1, 2)
        sh = np.asarray(L.shuffle_channel(_t(c), 2).numpy())
        np.testing.assert_allclose(sh[0, :, 0, 0], [0, 4, 2, 6])

    def test_temporal_shift(self):
        x = np.arange(2 * 2 * 4, dtype='float32').reshape(4, 4, 1, 1)
        out = np.asarray(L.temporal_shift(_t(x), seg_num=2,
                                          shift_ratio=0.25).numpy())
        assert out.shape == x.shape
        # channel 0 shifts backward: frame t takes t-1's value
        assert out[0, 0, 0, 0] == 0.0   # padding at t=0
        assert out[1, 0, 0, 0] == x[0, 0, 0, 0]

    def test_tensor_helpers(self):
        x = np.array([1.0, np.inf], 'float32')
        assert bool(np.asarray(L.has_inf(_t(x)).numpy()))
        assert not bool(np.asarray(L.has_nan(_t(x)).numpy()))
        assert not bool(np.asarray(L.isfinite(_t(x)).numpy()))
        assert np.asarray(L.eye(3).numpy()).shape == (3, 3)
        e = np.asarray(L.eye(2, batch_shape=[4]).numpy())
        assert e.shape == (4, 2, 2)
        np.testing.assert_allclose(
            np.asarray(L.range(0, 6, 2, 'int32').numpy()), [0, 2, 4])
        # FIRST-OCCURRENCE order like the reference, not sorted
        u, idx = L.unique(_t([2, 3, 3, 1], 'int64'))
        np.testing.assert_allclose(np.asarray(u.numpy()), [2, 3, 1])
        np.testing.assert_allclose(np.asarray(idx.numpy()),
                                   [0, 1, 1, 2])
        u, idx, cnt = L.unique_with_counts(_t([2, 3, 3, 1], 'int64'))
        np.testing.assert_allclose(np.asarray(u.numpy()), [2, 3, 1])
        np.testing.assert_allclose(np.asarray(cnt.numpy()), [1, 2, 1])

    def test_control_flow_helpers(self):
        a, b = _t([1.0]), _t([2.0])
        assert bool(np.asarray(L.less_than(a, b).numpy()))
        assert not bool(np.asarray(L.is_empty(a).numpy()))
        L.Assert(_t([1.0]) < _t([2.0]))
        with pytest.raises(AssertionError):
            L.Assert(_t([2.0]) < _t([1.0]), data=[a])

    def test_counter(self):
        c1 = int(np.asarray(
            L.autoincreased_step_counter('t_probe').numpy())[0])
        c2 = int(np.asarray(
            L.autoincreased_step_counter('t_probe').numpy())[0])
        assert c2 == c1 + 1


class TestLossesAndMetrics:
    def test_cos_sim(self):
        rs = np.random.RandomState(0)
        a = rs.randn(4, 8).astype('float32')
        b = rs.randn(4, 8).astype('float32')
        out = np.asarray(L.cos_sim(_t(a), _t(b)).numpy())
        ref = np.sum(a * b, 1, keepdims=True) / (
            np.linalg.norm(a, axis=1, keepdims=True)
            * np.linalg.norm(b, axis=1, keepdims=True))
        np.testing.assert_allclose(out, ref, rtol=1e-5)

    def test_smooth_l1(self):
        x = np.array([[0.1, 2.0]], 'float32')
        y = np.array([[0.0, 0.0]], 'float32')
        out = np.asarray(L.smooth_l1(_t(x), _t(y)).numpy())
        ref = 0.5 * 0.1 ** 2 + (2.0 - 0.5)
        np.testing.assert_allclose(out, [[ref]], rtol=1e-5)

    def test_log_loss(self):
        p = np.array([[0.8]], 'float32')
        y = np.array([[1.0]], 'float32')
        out = float(np.asarray(L.log_loss(_t(p), _t(y)).numpy()))
        np.testing.assert_allclose(out, -math.log(0.8 + 1e-4),
                                   rtol=1e-5)

    def test_dice_loss(self):
        p = np.array([[[0.0, 1.0], [1.0, 0.0]]], 'float32')
        y = np.array([[[1], [0]]], 'int64')
        out = float(np.asarray(L.dice_loss(_t(p, 'float32'),
                                           _t(y, 'int64')).numpy()))
        np.testing.assert_allclose(out, 0.0, atol=1e-5)

    def test_dice_loss_per_sample_mean(self):
        # per-sample dice averaged over the batch (reference
        # nn.py:7102), NOT a global pool
        p = np.array([[0.9, 0.9], [0.1, 0.05], [0.3, 0.2]],
                     'float32')[:, :, None].transpose(0, 2, 1)
        # shape [3, 1, 2]: one position, two classes
        y = np.array([[[0]], [[1]], [[1]]], 'int64')
        out = float(np.asarray(L.dice_loss(
            _t(p), _t(y, 'int64')).numpy()))
        ref = np.mean([1 - 2 * 0.9 / (0.9 + 0.9 + 1 + 1e-5),
                       1 - 2 * 0.05 / (0.1 + 0.05 + 1 + 1e-5),
                       1 - 2 * 0.2 / (0.3 + 0.2 + 1 + 1e-5)])
        np.testing.assert_allclose(out, ref, rtol=1e-5)

    def test_mean_iou(self):
        pred = np.array([0, 1, 1, 2], 'int64')
        lab = np.array([0, 1, 0, 2], 'int64')
        miou, wrong, correct = L.mean_iou(_t(pred, 'int64'),
                                          _t(lab, 'int64'), 3)
        # class ious: 0 -> 1/2, 1 -> 1/2, 2 -> 1/1
        np.testing.assert_allclose(float(np.asarray(miou.numpy())),
                                   (0.5 + 0.5 + 1.0) / 3, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(correct.numpy()),
                                   [1, 1, 1])
        # the reference counts BOTH sides of a mismatch (the [1,0]
        # miss adds wrong[0] AND wrong[1])
        np.testing.assert_allclose(np.asarray(wrong.numpy()),
                                   [1, 1, 0])

    def test_fsp_matrix(self):
        rs = np.random.RandomState(1)
        x = rs.randn(2, 3, 4, 4).astype('float32')
        y = rs.randn(2, 5, 4, 4).astype('float32')
        out = np.asarray(L.fsp_matrix(_t(x), _t(y)).numpy())
        assert out.shape == (2, 3, 5)
        ref = np.einsum('nchw,ndhw->ncd', x, y) / 16
        np.testing.assert_allclose(out, ref, rtol=1e-4)


class TestCtcAndCrf:
    def test_ctc_greedy_decoder(self):
        # argmax path: [a, a, blank, b] -> [a, b]
        C, blank = 3, 2
        probs = np.zeros((1, 4, C), 'float32')
        probs[0, 0, 0] = 1.0
        probs[0, 1, 0] = 1.0
        probs[0, 2, blank] = 1.0
        probs[0, 3, 1] = 1.0
        dec, lens = L.ctc_greedy_decoder(_t(probs), blank)
        d = np.asarray(dec.numpy())[0]
        n = int(np.asarray(lens.numpy())[0])
        assert n == 2
        np.testing.assert_allclose(d[:2], [0, 1])

    def test_linear_chain_crf_matches_brute_force(self):
        # with a FIXED transition, exp(-nll(path)) summed over every
        # label sequence must be exactly 1 (a normalized distribution)
        import itertools
        N, T, C = 1, 3, 2
        rs = np.random.RandomState(2)
        emit = rs.randn(N, T, C).astype('float32')
        trans = rs.randn(C + 2, C).astype('float32') * 0.3
        total = 0.0
        for path in itertools.product(range(C), repeat=T):
            p = np.array([list(path)], 'int64')
            v = float(np.asarray(L.linear_chain_crf(
                _t(emit), _t(p, 'int64'),
                transition=_t(trans)).numpy()).ravel()[0])
            total += math.exp(-v)
        np.testing.assert_allclose(total, 1.0, rtol=1e-4)

    def test_crf_train_decode_consistency(self):
        # the decoded path has the LOWEST nll among all paths
        import itertools
        N, T, C = 1, 4, 3
        rs = np.random.RandomState(5)
        emit = rs.randn(N, T, C).astype('float32')
        trans = rs.randn(C + 2, C).astype('float32') * 0.5
        best = np.asarray(L.crf_decoding(_t(emit),
                                         _t(trans)).numpy())[0]
        nlls = {}
        for path in itertools.product(range(C), repeat=T):
            p = np.array([list(path)], 'int64')
            nlls[path] = float(np.asarray(L.linear_chain_crf(
                _t(emit), _t(p, 'int64'),
                transition=_t(trans)).numpy()).ravel()[0])
        assert tuple(best.tolist()) == min(nlls, key=nlls.get)

    def test_crf_decoding_viterbi(self):
        # deterministic emissions dominate -> path = argmax(emit)
        emit = np.zeros((1, 3, 2), 'float32')
        emit[0, 0, 1] = 5.0
        emit[0, 1, 0] = 5.0
        emit[0, 2, 1] = 5.0
        trans = np.zeros((4, 2), 'float32')
        path = np.asarray(L.crf_decoding(_t(emit),
                                         _t(trans)).numpy())
        np.testing.assert_allclose(path[0], [1, 0, 1])


class TestPsroiPool:
    def test_position_sensitive_average(self):
        # C = oc * ph * pw = 1 * 2 * 2; each bin reads its own channel
        x = np.zeros((1, 4, 4, 4), 'float32')
        for c in range(4):
            x[0, c] = c + 1
        rois = np.array([[0.0, 0.0, 4.0, 4.0]], 'float32')
        out = np.asarray(L.psroi_pool(
            _t(x), _t(rois), output_channels=1, spatial_scale=1.0,
            pooled_height=2, pooled_width=2).numpy())
        assert out.shape == (1, 1, 2, 2)
        np.testing.assert_allclose(out[0, 0],
                                   [[1.0, 2.0], [3.0, 4.0]])


class TestResizeAndSampling:
    def test_resize_bilinear_shape(self):
        x = np.random.RandomState(3).rand(1, 2, 4, 4).astype('f4')
        out = np.asarray(L.resize_bilinear(
            _t(x), out_shape=[8, 8]).numpy())
        assert out.shape == (1, 2, 8, 8)

    def test_image_resize_short(self):
        x = np.random.RandomState(3).rand(1, 2, 4, 8).astype('f4')
        out = np.asarray(L.image_resize_short(_t(x), 6).numpy())
        assert out.shape == (1, 2, 6, 12)

    def test_random_crop(self):
        x = np.random.RandomState(4).rand(2, 8, 8).astype('f4')
        out = np.asarray(L.random_crop(_t(x), [4, 4],
                                       seed=7).numpy())
        assert out.shape == (2, 4, 4)

    def test_sampling_id(self):
        p = np.array([[0.0, 1.0, 0.0]] * 5, 'float32')
        ids = np.asarray(L.sampling_id(_t(p), seed=3).numpy())
        np.testing.assert_allclose(ids, [1] * 5)

    def test_batch_size_like_family(self):
        x = _t(np.zeros((5, 2), 'float32'))
        a = np.asarray(L.fill_constant_batch_size_like(
            x, [1, 3], 'float32', 9.0).numpy())
        assert a.shape == (5, 3) and (a == 9.0).all()
        b = np.asarray(L.uniform_random_batch_size_like(
            x, [1, 4]).numpy())
        assert b.shape == (5, 4)

    def test_add_position_encoding(self):
        x = np.zeros((1, 4, 6), 'float32')
        out = np.asarray(L.add_position_encoding(
            _t(x), alpha=1.0, beta=1.0).numpy())
        # position 0: sin(0)=0 for the first half, cos(0)=1 after
        np.testing.assert_allclose(out[0, 0, :3], [0, 0, 0],
                                   atol=1e-6)
        np.testing.assert_allclose(out[0, 0, 3:], [1, 1, 1],
                                   atol=1e-6)


class TestReviewFixes:
    def test_crf_decoding_is_the_static_nn_one(self):
        # the compat sweep must NOT shadow the pre-existing
        # implementation (which supports seq_len=)
        import inspect
        sig = inspect.signature(L.crf_decoding)
        assert 'seq_len' in sig.parameters

    def test_mul_keeps_leading_dims(self):
        x = np.arange(24, dtype='float32').reshape(2, 3, 4)
        y = np.arange(20, dtype='float32').reshape(4, 5)
        out = np.asarray(L.mul(_t(x), _t(y),
                               x_num_col_dims=2).numpy())
        assert out.shape == (2, 3, 5)
        np.testing.assert_allclose(out, x @ y, rtol=1e-5)

    def test_smooth_l1_outside_weight_alone(self):
        x = np.array([[2.0]], 'float32')
        y = np.array([[0.0]], 'float32')
        w = np.array([[0.5]], 'float32')
        out = float(np.asarray(L.smooth_l1(
            _t(x), _t(y), outside_weight=_t(w)).numpy()).ravel()[0])
        np.testing.assert_allclose(out, (2.0 - 0.5) * 0.5, rtol=1e-5)

    def test_add_position_encoding_odd_channels(self):
        x = np.zeros((1, 3, 5), 'float32')
        out = np.asarray(L.add_position_encoding(
            _t(x), 1.0, 1.0).numpy())
        assert out.shape == (1, 3, 5)
        assert np.isfinite(out).all()

    def test_random_crop_varies_across_calls(self):
        x = np.random.RandomState(5).rand(16, 16).astype('f4')
        crops = [np.asarray(L.random_crop(_t(x), [4, 4]).numpy())
                 for _ in builtins_range(6)]
        assert any(not np.array_equal(crops[0], c)
                   for c in crops[1:])


builtins_range = range
