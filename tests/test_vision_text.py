"""Vision models/transforms/datasets + text datasets + metrics +
distributions (SURVEY.md §2 items 17-24)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.vision import models, transforms, datasets
from paddle_tpu.vision.transforms import functional as TF
from paddle_tpu import text
from paddle_tpu.metric import Accuracy, Precision, Recall, Auc, accuracy
from paddle_tpu.distribution import (Normal, Uniform, Categorical,
                                     MultivariateNormalDiag)


def t(a):
    return paddle.to_tensor(np.asarray(a))


# -- models ------------------------------------------------------------------

def test_lenet_forward_and_grad():
    net = models.LeNet()
    x = t(np.random.randn(2, 1, 28, 28).astype('float32'))
    out = net(x)
    assert list(out.shape) == [2, 10]
    loss = out.sum()
    loss.backward()
    assert net.features[0].weight.grad is not None


def test_resnet18_tiny():
    net = models.resnet18(num_classes=4)
    x = t(np.random.randn(2, 3, 32, 32).astype('float32'))
    assert list(net(x).shape) == [2, 4]


def test_resnet_nhwc_matches_nchw():
    paddle.seed(0)
    a = models.resnet18(num_classes=3)
    paddle.seed(0)
    b = models.resnet18(num_classes=3, data_format='NHWC')
    b.set_state_dict(a.state_dict())
    a.eval()
    b.eval()
    x = np.random.randn(2, 3, 32, 32).astype('float32')
    ya = np.asarray(a(t(x)).value)
    yb = np.asarray(b(t(x.transpose(0, 2, 3, 1))).value)
    np.testing.assert_allclose(ya, yb, rtol=2e-4, atol=2e-4)


def test_resnet_s2d_stem_matches_standard():
    """The MLPerf-TPU space-to-depth stem is the SAME function as the
    7x7/s2 stem under the exact weight re-lay
    (space_to_depth_stem_weight) — proven here on CPU; whether it is
    faster on the chip is not measured."""
    from paddle_tpu.vision.models.resnet import (
        space_to_depth_stem_weight)
    paddle.seed(0)
    a = models.resnet18(num_classes=3, data_format='NHWC')
    paddle.seed(0)
    b = models.resnet18(num_classes=3, data_format='NHWC',
                        stem_space_to_depth=True)
    sd = a.state_dict()
    bsd = b.state_dict()
    for k in bsd:
        if k == 'conv1.weight':
            bsd[k] = t(space_to_depth_stem_weight(
                np.asarray(sd[k].value)))
        else:
            bsd[k] = sd[k]
    b.set_state_dict(bsd)
    a.eval()
    b.eval()
    x = np.random.RandomState(0).randn(2, 32, 32, 3).astype('float32')
    ya = np.asarray(a(t(x)).value)
    yb = np.asarray(b(t(x)).value)
    np.testing.assert_allclose(ya, yb, rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError):
        models.resnet18(stem_space_to_depth=True)   # NCHW forbidden


def test_mobilenet_v2_forward():
    net = models.mobilenet_v2(scale=0.35, num_classes=3)
    x = t(np.random.randn(1, 3, 32, 32).astype('float32'))
    assert list(net(x).shape) == [1, 3]


def test_vgg_structure():
    net = models.vgg11(num_classes=5)
    n_convs = sum(1 for _, l in net.named_sublayers()
                  if isinstance(l, nn.Conv2D))
    assert n_convs == 8


def test_model_state_dict_roundtrip():
    net = models.LeNet()
    sd = net.state_dict()
    net2 = models.LeNet()
    net2.set_state_dict(sd)
    x = t(np.random.randn(1, 1, 28, 28).astype('float32'))
    net.eval()
    net2.eval()
    np.testing.assert_allclose(np.asarray(net(x).value),
                               np.asarray(net2(x).value), rtol=1e-6)


# -- transforms --------------------------------------------------------------

def test_resize_shapes():
    img = np.random.randint(0, 256, (40, 60, 3), dtype=np.uint8)
    assert TF.resize(img, 20).shape == (20, 30, 3)
    assert TF.resize(img, (15, 25)).shape == (15, 25, 3)
    assert TF.resize(img, (15, 25), 'nearest').shape == (15, 25, 3)


def test_resize_bilinear_constant_image():
    img = np.full((10, 10, 1), 128, dtype=np.uint8)
    out = TF.resize(img, (4, 7))
    assert np.all(out == 128)


def test_flips_and_crop():
    img = np.arange(12, dtype=np.uint8).reshape(3, 4, 1)
    np.testing.assert_array_equal(TF.hflip(img), img[:, ::-1])
    np.testing.assert_array_equal(TF.vflip(img), img[::-1])
    c = TF.center_crop(img, (1, 2))
    assert c.shape == (1, 2, 1)


def test_normalize():
    img = np.ones((3, 2, 2), dtype=np.float32)
    out = TF.normalize(img, [1.0, 1.0, 1.0], [0.5, 0.5, 0.5], 'CHW')
    np.testing.assert_allclose(out, 0.0)


def test_to_tensor_and_compose():
    tr = transforms.Compose([transforms.Resize((8, 8)),
                             transforms.ToTensor()])
    img = np.random.randint(0, 256, (16, 16, 3), dtype=np.uint8)
    out = tr(img)
    assert out.shape == (3, 8, 8) and out.dtype == np.float32
    assert out.max() <= 1.0


def test_color_and_rotation_run():
    img = np.random.randint(0, 256, (12, 12, 3), dtype=np.uint8)
    assert TF.adjust_brightness(img, 1.3).shape == img.shape
    assert TF.adjust_contrast(img, 0.7).shape == img.shape
    assert TF.adjust_saturation(img, 1.1).shape == img.shape
    assert TF.adjust_hue(img, 0.2).shape == img.shape
    assert TF.rotate(img, 45).shape == img.shape
    assert TF.rotate(img, 90, expand=True).shape[0] >= 12
    g = TF.to_grayscale(img, 3)
    assert g.shape == img.shape
    assert np.all(g[:, :, 0] == g[:, :, 1])


def test_hue_identity():
    img = np.random.randint(0, 256, (8, 8, 3), dtype=np.uint8)
    out = TF.adjust_hue(img, 0.0)
    assert np.abs(out.astype(int) - img.astype(int)).max() <= 2


# -- datasets ----------------------------------------------------------------

def test_mnist_dataset():
    ds = datasets.MNIST(mode='train')
    img, label = ds[0]
    assert img.shape == (28, 28, 1) and label.shape == (1,)
    assert len(ds) > 100
    # deterministic across instantiations
    ds2 = datasets.MNIST(mode='train')
    np.testing.assert_array_equal(ds[5][0], ds2[5][0])


def test_cifar_datasets():
    for cls, ncls in [(datasets.Cifar10, 10), (datasets.Cifar100, 100)]:
        ds = cls(mode='test')
        img, label = ds[0]
        assert img.shape == (32, 32, 3)
        assert 0 <= int(label[0]) < ncls


def test_dataset_folder(tmp_path):
    for cls_name in ('cat', 'dog'):
        d = tmp_path / cls_name
        d.mkdir()
        for i in range(3):
            np.save(str(d / f'{i}.npy'),
                    np.random.randint(0, 256, (8, 8, 3), dtype=np.uint8))
    ds = datasets.DatasetFolder(str(tmp_path))
    assert len(ds) == 6
    assert ds.classes == ['cat', 'dog']
    img, label = ds[0]
    assert img.shape == (8, 8, 3) and label == 0
    flat = datasets.ImageFolder(str(tmp_path))
    assert len(flat) == 6


def test_voc2012():
    ds = datasets.VOC2012(mode='train')
    img, mask = ds[0]
    assert img.shape == (64, 64, 3) and mask.shape == (64, 64)
    assert mask.max() < 21


def test_text_datasets():
    imdb = text.Imdb(mode='train')
    doc, label = imdb[0]
    assert doc.dtype == np.int64 and label.shape == (1,)
    iml = text.Imikolov(data_type='NGRAM', window_size=3, mode='test')
    assert len(iml[0]) == 3
    uci = text.UCIHousing(mode='train')
    feats, price = uci[0]
    assert feats.shape == (13,) and price.shape == (1,)
    assert len(uci) == 404
    ml = text.Movielens(mode='train')
    assert len(ml[0]) == 8
    conll = text.Conll05st()
    assert len(conll[0]) == 9
    wmt = text.WMT16(mode='train')
    src, trg, trg_next = wmt[0]
    assert trg[0] == 0 and trg_next[-1] == 1  # BOS / EOS


# -- metrics -----------------------------------------------------------------

def test_accuracy_metric():
    m = Accuracy()
    pred = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]], dtype='float32')
    label = np.array([[1], [0], [0]])
    correct = m.compute(t(pred), t(label))
    m.update(correct)
    assert abs(m.accumulate() - 2.0 / 3.0) < 1e-6
    m.reset()
    assert m.accumulate() == 0.0


def test_accuracy_topk():
    m = Accuracy(topk=(1, 2))
    pred = np.array([[0.5, 0.3, 0.2], [0.1, 0.4, 0.5]], dtype='float32')
    label = np.array([[1], [1]])
    m.update(m.compute(t(pred), t(label)))
    top1, top2 = m.accumulate()
    assert abs(top1 - 0.0) < 1e-6 and abs(top2 - 1.0) < 1e-6


def test_functional_accuracy():
    pred = np.array([[0.9, 0.1], [0.2, 0.8]], dtype='float32')
    label = np.array([[0], [1]])
    acc = accuracy(t(pred), t(label), k=1)
    assert abs(float(np.asarray(acc.value).reshape(())) - 1.0) < 1e-6


def test_precision_recall():
    p = Precision()
    r = Recall()
    preds = np.array([0.9, 0.8, 0.2, 0.6])
    labels = np.array([1, 0, 1, 1])
    p.update(preds, labels)
    r.update(preds, labels)
    assert abs(p.accumulate() - 2.0 / 3.0) < 1e-6
    assert abs(r.accumulate() - 2.0 / 3.0) < 1e-6


def test_auc_perfect_and_random():
    m = Auc()
    scores = np.array([0.9, 0.8, 0.2, 0.1])
    labels = np.array([1, 1, 0, 0])
    m.update(scores, labels)
    assert m.accumulate() > 0.99
    m.reset()
    m.update(np.array([0.6]* 4), labels)
    assert abs(m.accumulate() - 0.5) < 0.05


# -- distributions -----------------------------------------------------------

def test_normal_log_prob_and_kl():
    d = Normal(0.0, 1.0)
    lp = float(np.asarray(d.log_prob(t(np.float32(0.0))).value))
    assert abs(lp - (-0.5 * np.log(2 * np.pi))) < 1e-5
    d2 = Normal(1.0, 1.0)
    kl = float(np.asarray(d.kl_divergence(d2).value))
    assert abs(kl - 0.5) < 1e-5
    paddle.seed(0)
    s = d.sample([1000])
    assert abs(float(np.asarray(s.value).mean())) < 0.2


def test_uniform():
    d = Uniform(0.0, 2.0)
    assert abs(float(np.asarray(d.entropy().value)) - np.log(2.0)) < 1e-6
    lp = float(np.asarray(d.log_prob(t(np.float32(1.0))).value))
    assert abs(lp - np.log(0.5)) < 1e-6
    s = np.asarray(d.sample([500]).value)
    assert s.min() >= 0.0 and s.max() <= 2.0


def test_categorical():
    logits = np.log(np.array([0.2, 0.3, 0.5], dtype='float32'))
    d = Categorical(logits)
    lp = float(np.asarray(d.log_prob(t(np.int64(2))).value))
    assert abs(lp - np.log(0.5)) < 1e-5
    ent = float(np.asarray(d.entropy().value))
    expected = -sum(p * np.log(p) for p in [0.2, 0.3, 0.5])
    assert abs(ent - expected) < 1e-5
    paddle.seed(0)
    s = np.asarray(d.sample([2000]).value)
    assert abs((s == 2).mean() - 0.5) < 0.1


def test_multivariate_normal_diag():
    """Entropy and KL vs closed forms (reference
    fluid/layers/distributions.py:531; scale is the DIAGONAL
    covariance matrix)."""
    cov_a = np.diag([0.5, 2.0]).astype('float32')
    cov_b = np.diag([1.0, 1.0]).astype('float32')
    a = MultivariateNormalDiag(np.array([0.3, 0.5], 'float32'), cov_a)
    b = MultivariateNormalDiag(np.array([0.0, 0.0], 'float32'), cov_b)
    k = 2
    want_ent = 0.5 * (k * (1 + np.log(2 * np.pi))
                      + np.log(0.5 * 2.0))
    assert abs(float(np.asarray(a.entropy().value)) - want_ent) < 1e-5
    # KL(a||b) for diagonal covariances
    d = np.array([0.0, 0.0]) - np.array([0.3, 0.5])
    want_kl = 0.5 * ((0.5 + 2.0) + d @ d - k
                     + np.log(1.0 / (0.5 * 2.0)))
    got_kl = float(np.asarray(a.kl_divergence(b).value))
    assert abs(got_kl - want_kl) < 1e-5
    import pytest as _p
    with _p.raises(TypeError):
        a.kl_divergence(Normal(0.0, 1.0))
    # log-domain determinant: high-dim small variances must not
    # underflow to -inf (prod(0.1^60) == 0 in f32)
    big = MultivariateNormalDiag(np.zeros(60, 'float32'),
                                 np.diag([0.1] * 60).astype('float32'))
    ent = float(np.asarray(big.entropy().value))
    want = 0.5 * (60 * (1 + np.log(2 * np.pi)) + 60 * np.log(0.1))
    assert np.isfinite(ent) and abs(ent - want) < 1e-3
    # 1.x namespace parity: fluid.layers exports all four classes
    import paddle_tpu.fluid as fluid
    for n in ('Normal', 'Uniform', 'Categorical',
              'MultivariateNormalDiag'):
        assert hasattr(fluid.layers, n), n


def test_seed_reproduces_sampling_and_transforms():
    paddle.seed(42)
    a = np.asarray(Normal(0.0, 1.0).sample([4]).value)
    flip_a = transforms.RandomHorizontalFlip(0.5)
    img = np.arange(48, dtype=np.uint8).reshape(4, 4, 3)
    seq_a = [flip_a(img).tobytes() for _ in range(8)]

    paddle.seed(42)
    b = np.asarray(Normal(0.0, 1.0).sample([4]).value)
    seq_b = [flip_a(img).tobytes() for _ in range(8)]
    np.testing.assert_array_equal(a, b)
    assert seq_a == seq_b


def test_auc_vectorized_matches_loop():
    rng = np.random.RandomState(3)
    scores = rng.rand(500)
    labels = (scores + rng.randn(500) * 0.3 > 0.5).astype(int)
    m = Auc(num_thresholds=255)
    m.update(scores, labels)
    # brute-force pairwise AUC
    pos, neg = scores[labels == 1], scores[labels == 0]
    brute = (pos[:, None] > neg[None, :]).mean() + \
        0.5 * (pos[:, None] == neg[None, :]).mean()
    assert abs(m.accumulate() - brute) < 0.02
