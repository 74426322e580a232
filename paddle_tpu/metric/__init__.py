"""Metrics: Accuracy / Precision / Recall / Auc.

Reference analogue: python/paddle/metric/metrics.py (Metric, Accuracy,
Precision, Recall, Auc, paddle.metric.accuracy).

Jit-safe state discipline (SURVEY §2#21): `compute` runs INSIDE the
compiled eval step and reduces the batch to a tiny statistic array
(correct-counts, tp/fp, AUC histogram buckets); `update` adds that
statistic into a device-resident jnp state with NO host readback —
lazy device ops only, so `hapi.Model.evaluate` performs zero
device→host syncs per batch (each one stalls the dispatch
pipeline).  The only host sync is `accumulate()` at the end of
evaluation.  The legacy eager signatures (`update(preds, labels)`
with raw predictions) still work and route through the same compute.
"""
import abc

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor

__all__ = ['Metric', 'Accuracy', 'Precision', 'Recall', 'Auc', 'accuracy']


def _to_np(x):
    if isinstance(x, Tensor):
        return np.asarray(x.value)
    return np.asarray(x)


def _to_jnp(x):
    if isinstance(x, Tensor):
        return x.value
    return jnp.asarray(x)


class _LongCounter:
    """Device-resident EXACT integer accumulator for streaming metric
    states: two int32 limbs (`hi` in units of 2^16), with the carry
    fold every `_FOLD_EVERY` adds done ON DEVICE — `add` is always a
    lazy jnp op, never a host sync, and the representable total
    (~1.4e14 per element) outlives any eval stream.  (A single f32
    state saturates at 2^24 and a single int32 wraps at 2^31; the one
    host sync is `read()` at accumulate time.)"""

    _FOLD_EVERY = 1024

    def __init__(self, shape):
        self.lo = jnp.zeros(shape, jnp.int32)
        self.hi = jnp.zeros(shape, jnp.int32)
        self._adds = 0

    def add(self, x):
        self.lo = self.lo + x.astype(jnp.int32)
        self._adds += 1
        if self._adds >= self._FOLD_EVERY:
            carry = self.lo >> 16          # still lazy device math
            self.hi = self.hi + carry
            self.lo = self.lo - (carry << 16)
            self._adds = 0

    def read(self):
        """Host int64 totals — the single device→host sync."""
        return ((np.asarray(self.hi).astype(np.int64) << 16)
                + np.asarray(self.lo).astype(np.int64))


class Metric(abc.ABC):
    def __init__(self):
        pass

    @abc.abstractmethod
    def reset(self):
        raise NotImplementedError

    @abc.abstractmethod
    def update(self, *args):
        raise NotImplementedError

    @abc.abstractmethod
    def accumulate(self):
        raise NotImplementedError

    @abc.abstractmethod
    def name(self):
        raise NotImplementedError

    def compute(self, *args):
        """Device-side pre-computation; runs inside the compiled step."""
        return args


class Accuracy(Metric):
    def __init__(self, topk=(1,), name=None, *args, **kwargs):
        super().__init__()
        self.topk = (topk,) if isinstance(topk, int) else tuple(topk)
        self.maxk = max(self.topk)
        self._name = name or 'acc'
        self.reset()

    def compute(self, pred, label, *args):
        """Return correctness matrix [N, maxk] (jit-safe)."""
        pred = _to_jnp(pred)
        label = _to_jnp(label)
        # lax.top_k, not a full argsort: O(C log k) and no [.., C]
        # sorted-index tensor on the eval step's critical path.
        # k clamps to the class count (top_k raises where the old
        # argsort slice silently clamped, e.g. topk=(1,5) on a
        # 2-class head)
        _, pred_idx = jax.lax.top_k(
            pred, min(self.maxk, pred.shape[-1]))
        if label.ndim == pred.ndim:  # one-hot or column labels
            if label.shape[-1] == 1:
                label = label[..., 0]
            else:
                label = jnp.argmax(label, axis=-1)
        return (pred_idx == label[..., None]).astype(jnp.float32)

    def update(self, correct, *args):
        """Accumulate per-topk correct counts as LAZY device adds (no
        float() readback); returns the batch accuracies as jnp scalars
        (callers that print force the sync, not the update)."""
        correct = _to_jnp(correct)
        n = correct.shape[0]
        nums = jnp.stack([jnp.sum(correct[..., :k]) for k in self.topk])
        self.total.add(jnp.round(nums))
        self.count += n
        accs = [nums[i] / max(1, n) for i in range(len(self.topk))]
        return accs[0] if len(accs) == 1 else accs

    def reset(self):
        self.total = _LongCounter(len(self.topk))
        self.count = 0

    def accumulate(self):
        tot = self.total.read()   # the single host sync
        res = [float(t) / max(1, self.count) for t in tot]
        return res[0] if len(res) == 1 else res

    def name(self):
        if len(self.topk) == 1:
            return self._name
        return ['{}_top{}'.format(self._name, k) for k in self.topk]


class Precision(Metric):
    """Binary precision over thresholded predictions."""

    _STAT_LEN = 2   # (tp, fp)

    def __init__(self, name='precision', *args, **kwargs):
        super().__init__()
        self._name = name
        self.reset()

    def compute(self, preds, labels, *args):
        """[tp, fp] of the batch as a jnp stat (jit-safe)."""
        p = _to_jnp(preds).reshape(-1)
        y = _to_jnp(labels).reshape(-1)
        pred_pos = p > 0.5
        tp = jnp.sum(pred_pos & (y == 1))
        fp = jnp.sum(pred_pos & (y != 1))
        return jnp.stack([tp, fp]).astype(jnp.int32)

    def update(self, stat, labels=None):
        """`stat` is compute()'s [tp, fp]; the legacy eager call
        update(preds, labels) routes through compute first."""
        if labels is not None:
            stat = self.compute(stat, labels)
        self._stat.add(_to_jnp(stat))

    def reset(self):
        self._stat = _LongCounter(2)

    def accumulate(self):
        tp, fp = self._stat.read()
        denom = tp + fp
        return float(tp / denom) if denom else 0.0

    def name(self):
        return self._name


class Recall(Metric):
    """Binary recall over thresholded predictions."""

    def __init__(self, name='recall', *args, **kwargs):
        super().__init__()
        self._name = name
        self.reset()

    def compute(self, preds, labels, *args):
        """[tp, fn] of the batch as a jnp stat (jit-safe)."""
        p = _to_jnp(preds).reshape(-1)
        y = _to_jnp(labels).reshape(-1)
        pred_pos = p > 0.5
        tp = jnp.sum(pred_pos & (y == 1))
        fn = jnp.sum(~pred_pos & (y == 1))
        return jnp.stack([tp, fn]).astype(jnp.int32)

    def update(self, stat, labels=None):
        if labels is not None:
            stat = self.compute(stat, labels)
        self._stat.add(_to_jnp(stat))

    def reset(self):
        self._stat = _LongCounter(2)

    def accumulate(self):
        tp, fn = self._stat.read()
        denom = tp + fn
        return float(tp / denom) if denom else 0.0

    def name(self):
        return self._name


class Auc(Metric):
    """ROC AUC via histogram buckets (streaming-friendly).  The bucket
    histograms are jnp state summed in-place per batch; the trapezoid
    walk happens once, at accumulate()."""

    def __init__(self, curve='ROC', num_thresholds=4095, name='auc',
                 *args, **kwargs):
        super().__init__()
        self.curve = curve
        self.num_thresholds = num_thresholds
        self._name = name
        self.reset()

    def compute(self, preds, labels, *args):
        """Batch bucket histograms stacked [2, T+1] (pos, neg) — a
        scatter-add inside the compiled step."""
        p = _to_jnp(preds)
        y = _to_jnp(labels).reshape(-1)
        if p.ndim == 2 and p.shape[1] == 2:
            scores = p[:, 1]
        else:
            scores = p.reshape(-1)
        n = self.num_thresholds + 1
        buckets = jnp.clip(
            (scores * self.num_thresholds).astype(jnp.int32),
            0, self.num_thresholds)
        pos = (y != 0).astype(jnp.int32)
        pos_hist = jnp.zeros(n, jnp.int32).at[buckets].add(pos)
        neg_hist = jnp.zeros(n, jnp.int32).at[buckets].add(1 - pos)
        return jnp.stack([pos_hist, neg_hist])

    def update(self, stat, labels=None):
        """`stat` is compute()'s [2, T+1] histogram pair; the legacy
        eager call update(preds, labels) routes through compute.  The
        state is a _LongCounter: exact int64-range totals with every
        add (and the periodic carry fold) staying ON device."""
        if labels is not None:
            stat = self.compute(stat, labels)
        self._stat.add(_to_jnp(stat))

    def reset(self):
        self._stat = _LongCounter((2, self.num_thresholds + 1))

    @property
    def _stat_pos(self):
        """Host view of the positive buckets (fleet.metrics.auc and
        legacy consumers read these)."""
        return self._stat.read()[0]

    @property
    def _stat_neg(self):
        return self._stat.read()[1]

    def accumulate(self):
        # walk thresholds high->low accumulating TP/FP; trapezoid rule
        stat = self._stat.read()   # the single host sync
        stat_pos, stat_neg = stat[0], stat[1]
        tot_pos = float(stat_pos.sum())
        tot_neg = float(stat_neg.sum())
        if tot_pos == 0 or tot_neg == 0:
            return 0.0
        tp = fp = 0.0
        auc = 0.0
        prev_tpr = prev_fpr = 0.0
        for b in range(self.num_thresholds, -1, -1):
            tp += float(stat_pos[b])
            fp += float(stat_neg[b])
            tpr, fpr = tp / tot_pos, fp / tot_neg
            auc += (fpr - prev_fpr) * (tpr + prev_tpr) / 2.0
            prev_tpr, prev_fpr = tpr, fpr
        return auc

    def name(self):
        return self._name


def accuracy(input, label, k=1, correct=None, total=None, name=None):
    """Functional top-k accuracy (reference: paddle.metric.accuracy)."""
    x = input.value if isinstance(input, Tensor) else jnp.asarray(input)
    y = label.value if isinstance(label, Tensor) else jnp.asarray(label)
    _, pred_idx = jax.lax.top_k(x, min(k, x.shape[-1]))
    if y.ndim == x.ndim:
        if y.shape[-1] == 1:
            y = y[..., 0]
        else:
            y = jnp.argmax(y, axis=-1)
    correct_mat = (pred_idx == y[..., None]).any(axis=-1)
    return Tensor(jnp.mean(correct_mat.astype(jnp.float32), keepdims=True))
