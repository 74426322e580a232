"""Loss functionals.

Reference analogue: /root/reference/python/paddle/nn/functional/loss.py
(softmax_with_cross_entropy fused kernel etc.).  TPU-native: fused
log_softmax+gather formulation; XLA keeps it one kernel.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ...core.dispatch import apply
from ...tensor._helpers import wrap

__all__ = [
    'cross_entropy', 'softmax_with_cross_entropy', 'binary_cross_entropy',
    'binary_cross_entropy_with_logits', 'mse_loss', 'l1_loss', 'nll_loss',
    'kl_div', 'smooth_l1_loss', 'margin_ranking_loss', 'ctc_loss',
    'hinge_embedding_loss', 'cosine_embedding_loss', 'square_error_cost',
    'sigmoid_focal_loss', 'log_loss',
]


def _reduce(v, reduction):
    if reduction == 'mean':
        return jnp.mean(v)
    if reduction == 'sum':
        return jnp.sum(v)
    return v


@jax.custom_vjp
def _softmax_nll(x, lab):
    """Per-token -log_softmax(x)[lab] over the LAST axis.

    The autodiff backward of the take_along_axis gather is a
    scatter-add into the full [N, V] buffer — serialized on TPU; the
    unfused GPT-2 train step measured ~8x slower than expected at
    vocab shape [8192, 50304] with it on the path (an earlier round's
    chip session; tests/test_fused_ce.py::TestDenseCEBackward compares
    the two).  The custom backward emits the
    classic softmax-CE gradient (softmax - one_hot) * g as dense
    elementwise math, and recomputes softmax from the saved logits
    instead of keeping the f32 log-probs residual alive.
    """
    logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]


def _softmax_nll_fwd(x, lab):
    xf = x.astype(jnp.float32)
    lse = jax.nn.logsumexp(xf, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(xf, lab[..., None], axis=-1)
    return (lse - picked)[..., 0], (x, lab, lse)


def _softmax_nll_bwd(res, g):
    x, lab, lse = res
    p = jnp.exp(x.astype(jnp.float32) - lse)
    oh = lab[..., None] == jnp.arange(x.shape[-1], dtype=lab.dtype)
    dx = (p - oh.astype(p.dtype)) * g[..., None]
    return dx.astype(x.dtype), np.zeros(np.shape(lab), jax.dtypes.float0)


_softmax_nll.defvjp(_softmax_nll_fwd, _softmax_nll_bwd)


@jax.custom_vjp
def _pick_nll(logp, lab):
    """-logp[..., lab] over the last axis, with a dense -one_hot*g
    backward (the autodiff gather backward is a serialized scatter on
    TPU, same pathology as _softmax_nll)."""
    return -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]


def _pick_nll_fwd(logp, lab):
    # residual carries class count + dtype as a [C]-zeros template
    # (custom_vjp residuals must be arrays, not dtype objects)
    tmpl = jnp.zeros((logp.shape[-1],), logp.dtype)
    return _pick_nll(logp, lab), (lab, tmpl)


def _pick_nll_bwd(res, g):
    lab, tmpl = res
    oh = lab[..., None] == jnp.arange(tmpl.shape[0], dtype=lab.dtype)
    dlogp = jnp.where(oh, -g[..., None], 0.0).astype(tmpl.dtype)
    return dlogp, np.zeros(np.shape(lab), jax.dtypes.float0)


_pick_nll.defvjp(_pick_nll_fwd, _pick_nll_bwd)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction='mean', soft_label=False, axis=-1,
                  use_softmax=True, name=None):
    ins = [wrap(input), wrap(label)]
    if weight is not None:
        ins.append(wrap(weight))

    def fn(logits, lab, *maybe_w):
        if soft_label:
            if use_softmax:
                logp = jax.nn.log_softmax(logits, axis=axis)
            else:
                logp = jnp.log(jnp.maximum(logits, 1e-30))
            per = -jnp.sum(lab * logp, axis=axis)
            if maybe_w:
                per = per * jnp.sum(lab * maybe_w[0], axis=axis)
            return _reduce(per, reduction)
        lab_i = lab.astype(jnp.int32)
        if lab_i.ndim == logits.ndim:
            lab_i = jnp.squeeze(lab_i, axis=axis)
        safe = jnp.where(lab_i == ignore_index, 0, lab_i)
        if use_softmax and axis in (-1, logits.ndim - 1):
            # stays f32 through the reduction (bf16 accumulation over
            # thousands of tokens rounds the sum AND the mask-count
            # denominator); only the final result drops back
            per = _softmax_nll(logits, safe)
        elif axis in (-1, logits.ndim - 1):
            # prob-input path, same dense backward as the softmax one
            logp = jnp.log(jnp.maximum(logits, 1e-30))
            per = _pick_nll(logp, safe).astype(jnp.float32)
        else:
            if use_softmax:
                logp = jax.nn.log_softmax(logits, axis=axis)
            else:
                logp = jnp.log(jnp.maximum(logits, 1e-30))
            per = -jnp.take_along_axis(
                logp, safe[..., None], axis=axis)[..., 0]
            per = per.astype(jnp.float32)
        mask = (lab_i != ignore_index)
        per = jnp.where(mask, per, 0.0)
        out_dtype = logits.dtype
        if maybe_w:
            w = maybe_w[0][safe]
            per = per * jnp.where(mask, w, 0.0)
            if reduction == 'mean':
                denom = jnp.sum(
                    jnp.where(mask, w, 0.0).astype(jnp.float32))
                return (jnp.sum(per)
                        / jnp.maximum(denom, 1e-12)).astype(out_dtype)
        if reduction == 'mean':
            denom = jnp.maximum(jnp.sum(mask.astype(per.dtype)), 1.0)
            return (jnp.sum(per) / denom).astype(out_dtype)
        return _reduce(per, reduction).astype(out_dtype)

    return apply(fn, *ins, op_name='cross_entropy')


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction='none',
                         axis=axis)
    from .activation import softmax as _softmax
    # reference keeps the trailing 1-dim on hard labels
    if not soft_label:
        from ...tensor.manipulation import unsqueeze
        loss = unsqueeze(loss, axis)
    if return_softmax:
        return loss, _softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction='mean',
                         name=None):
    ins = [wrap(input), wrap(label)]
    if weight is not None:
        ins.append(wrap(weight))

    def fn(p, y, *maybe_w):
        p = jnp.clip(p, 1e-12, 1.0 - 1e-12)
        per = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if maybe_w:
            per = per * maybe_w[0]
        return _reduce(per, reduction)

    return apply(fn, *ins, op_name='binary_cross_entropy')


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction='mean', pos_weight=None,
                                     name=None):
    ins = [wrap(logit), wrap(label)]
    if weight is not None:
        ins.append(wrap(weight))
    if pos_weight is not None:
        ins.append(wrap(pos_weight))

    def fn(z, y, *extra):
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = extra[i]; i += 1
        if pos_weight is not None:
            pw = extra[i]
        # stable: max(z,0) - z*y + log(1+exp(-|z|)), with pos_weight
        if pw is not None:
            log_sig = jax.nn.log_sigmoid(z)
            log_sig_neg = jax.nn.log_sigmoid(-z)
            per = -(pw * y * log_sig + (1 - y) * log_sig_neg)
        else:
            per = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        if w is not None:
            per = per * w
        return _reduce(per, reduction)

    return apply(fn, *ins, op_name='bce_with_logits')


def mse_loss(input, label, reduction='mean', name=None):
    return apply(lambda a, b: _reduce(jnp.square(a - b), reduction),
                 wrap(input), wrap(label), op_name='mse_loss')


def square_error_cost(input, label):
    return apply(lambda a, b: jnp.square(a - b), wrap(input), wrap(label),
                 op_name='square_error_cost')


def l1_loss(input, label, reduction='mean', name=None):
    return apply(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                 wrap(input), wrap(label), op_name='l1_loss')


def nll_loss(input, label, weight=None, ignore_index=-100, reduction='mean',
             name=None):
    ins = [wrap(input), wrap(label)]
    if weight is not None:
        ins.append(wrap(weight))

    def fn(logp, lab, *maybe_w):
        if logp.ndim > 2:
            # reference contract: classes live at axis 1 for
            # (N, C, d1..dK) inputs with (N, d1..dK) labels
            logp = jnp.moveaxis(logp, 1, -1)
        lab_i = lab.astype(jnp.int32)
        safe = jnp.where(lab_i == ignore_index, 0, lab_i)
        per = _pick_nll(logp, safe)
        mask = lab_i != ignore_index
        per = jnp.where(mask, per, 0.0)
        if maybe_w:
            w = maybe_w[0][safe] * mask.astype(logp.dtype)
            if reduction == 'mean':
                return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-12)
            per = per * w
        if reduction == 'mean':
            return jnp.sum(per) / jnp.maximum(
                jnp.sum(mask.astype(logp.dtype)), 1.0)
        return _reduce(per, reduction)

    return apply(fn, *ins, op_name='nll_loss')


def kl_div(input, label, reduction='mean', name=None):
    def fn(logp, y):
        per = y * (jnp.log(jnp.maximum(y, 1e-30)) - logp)
        if reduction == 'batchmean':
            return jnp.sum(per) / logp.shape[0]
        return _reduce(per, reduction)
    return apply(fn, wrap(input), wrap(label), op_name='kl_div')


def smooth_l1_loss(input, label, reduction='mean', delta=1.0, name=None):
    def fn(a, b):
        d = a - b
        ad = jnp.abs(d)
        per = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
        return _reduce(per, reduction)
    return apply(fn, wrap(input), wrap(label), op_name='smooth_l1_loss')


def margin_ranking_loss(input, other, label, margin=0.0, reduction='mean',
                        name=None):
    def fn(a, b, y):
        per = jnp.maximum(0.0, -y * (a - b) + margin)
        return _reduce(per, reduction)
    return apply(fn, wrap(input), wrap(other), wrap(label),
                 op_name='margin_ranking_loss')


def hinge_embedding_loss(input, label, margin=1.0, reduction='mean',
                         name=None):
    def fn(a, y):
        per = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce(per, reduction)
    return apply(fn, wrap(input), wrap(label),
                 op_name='hinge_embedding_loss')


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction='mean', name=None):
    def fn(a, b, y):
        cos = jnp.sum(a * b, -1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        per = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(per, reduction)
    return apply(fn, wrap(input1), wrap(input2), wrap(label),
                 op_name='cosine_embedding_loss')


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction='sum', name=None):
    ins = [wrap(logit), wrap(label)]
    if normalizer is not None:
        ins.append(wrap(normalizer))

    def fn(z, y, *maybe_n):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        per = a_t * ((1 - p_t) ** gamma) * ce
        if maybe_n:
            per = per / maybe_n[0]
        return _reduce(per, reduction)

    return apply(fn, *ins, op_name='sigmoid_focal_loss')


def log_loss(input, label, epsilon=1e-4, name=None):
    def fn(p, y):
        return -(y * jnp.log(p + epsilon) +
                 (1 - y) * jnp.log(1 - p + epsilon))
    return apply(fn, wrap(input), wrap(label), op_name='log_loss')


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction='mean'):
    """CTC via the standard forward algorithm in log space, lax.scan over
    time — compiler-friendly (no per-step Python), cf. the reference's
    warp-ctc kernel (paddle/fluid/operators/warpctc_op.cc)."""
    def fn(lp, lab, in_len, lab_len):
        # lp: [T, B, C] log-probs; lab: [B, S]
        T, B, C = lp.shape
        S = lab.shape[1]
        ext = jnp.full((B, 2 * S + 1), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab.astype(jnp.int32))
        L = 2 * S + 1
        neg_inf = jnp.asarray(-1e30, lp.dtype)

        init = jnp.full((B, L), neg_inf)
        init = init.at[:, 0].set(lp[0, :, blank])
        init = init.at[:, 1].set(
            jnp.take_along_axis(lp[0], ext[:, 1:2], axis=1)[:, 0])

        same = jnp.concatenate(
            [jnp.ones((B, 2), bool),
             ext[:, 2:] == ext[:, :-2]], axis=1)

        def step(alpha, xs):
            lp_t, t = xs
            a0 = alpha
            a1 = jnp.concatenate([jnp.full((B, 1), neg_inf),
                                  alpha[:, :-1]], axis=1)
            a2 = jnp.concatenate([jnp.full((B, 2), neg_inf),
                                  alpha[:, :-2]], axis=1)
            a2 = jnp.where(same, neg_inf, a2)
            m = jnp.maximum(jnp.maximum(a0, a1), a2)
            s = (jnp.exp(a0 - m) + jnp.exp(a1 - m) + jnp.exp(a2 - m))
            merged = m + jnp.log(jnp.maximum(s, 1e-37))
            emit = jnp.take_along_axis(lp_t, ext, axis=1)
            new = merged + emit
            # freeze rows whose sequence already ended (t >= input_length)
            active = (t < in_len.astype(jnp.int32))[:, None]
            return jnp.where(active, new, alpha), None

        alpha_T, _ = jax.lax.scan(
            step, init, (lp[1:], jnp.arange(1, T, dtype=jnp.int32)))
        # final: sum of positions L-1 and L-2 (adjusted by label length)
        idx_last = 2 * lab_len.astype(jnp.int32)
        idx_prev = idx_last - 1
        aL = jnp.take_along_axis(alpha_T, idx_last[:, None], axis=1)[:, 0]
        aP = jnp.take_along_axis(alpha_T, jnp.maximum(idx_prev, 0)[:, None],
                                 axis=1)[:, 0]
        m = jnp.maximum(aL, aP)
        ll = m + jnp.log(jnp.exp(aL - m) + jnp.exp(aP - m))
        per = -ll
        if reduction == 'mean':
            return jnp.mean(per / jnp.maximum(lab_len.astype(lp.dtype), 1.0))
        return _reduce(per, reduction)

    return apply(fn, wrap(log_probs), wrap(labels), wrap(input_lengths),
                 wrap(label_lengths), op_name='ctc_loss')


_hsigmoid_trees = {}


def _hsigmoid_default_tree(C):
    """Complete-binary-tree path tables (heap layout: root=1, leaf for
    class c at heap index C+c, internal node n -> weight row n-1),
    cached per num_classes — hierarchical sigmoid exists for huge C,
    so the O(C log C) host walk must run once, not per step."""
    import numpy as np_
    if C in _hsigmoid_trees:
        return _hsigmoid_trees[C]
    L = max(int(np_.ceil(np_.log2(max(C, 2)))), 1)
    tbl = np_.full((C, L), -1, np_.int64)
    code = np_.zeros((C, L), np_.float32)
    for c in range(C):
        node = C + c
        path = []
        while node > 1:
            parent = node // 2
            path.append((parent - 1, float(node % 2)))
            node = parent
        for k, (p, b) in enumerate(reversed(path)):
            if k < L:
                tbl[c, k] = p
                code[c, k] = b
    _hsigmoid_trees[C] = (tbl, code)
    return tbl, code


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference: nn/functional/loss.py::
    hsigmoid_loss over the hsigmoid op).  Default tree: the complete
    binary tree over num_classes the reference builds — precomputed
    HOST-side as static [C, L] path-node/code tables, so the on-device
    work is two gathers + one BCE reduce (no per-class python).
    Custom trees come in via path_table/path_code [N, L] (or [C, L]),
    -1 padded."""
    import numpy as np_
    x, lb = wrap(input), wrap(label)
    w = wrap(weight)
    ins = [x, lb, w]
    if bias is not None:
        ins.append(wrap(bias))

    if path_table is None:
        path_table, path_code = _hsigmoid_default_tree(int(num_classes))
    pt = jnp.asarray(np_.asarray(path_table, np_.int64))
    pc = jnp.asarray(np_.asarray(path_code, np_.float32))

    def fn(v, y, wv, *b):
        y = y.reshape(v.shape[0]).astype(jnp.int32)
        nodes = pt[y]                       # [B, L]
        codes = pc[y]                       # [B, L]
        valid = (nodes >= 0).astype(v.dtype)
        safe = jnp.maximum(nodes, 0)
        wrow = wv[safe]                     # [B, L, D]
        logits = jnp.einsum('bd,bld->bl', v, wrow)
        if b:
            logits = logits + b[0].reshape(-1)[safe]
        # BCE with target = code bit
        ls = jax.nn.log_sigmoid(logits)
        per = -(codes * ls + (1 - codes) * (ls - logits))
        return (per * valid).sum(axis=-1, keepdims=True)

    return apply(fn, *ins, op_name='hsigmoid_loss')


__all__ += ['hsigmoid_loss']


def dice_loss(input, label, epsilon=1e-5, name=None):
    """Dice loss for segmentation (reference
    fluid/layers/nn.py dice_loss): label [..., 1] int is one-hotted to
    input's class dim; per-sample dice over all non-batch dims."""
    input = wrap(input)
    label = wrap(label)
    n_cls = input.shape[-1]

    def fn(x, lab):
        if lab.shape and lab.shape[-1] == 1:
            lab = lab.squeeze(-1)
        oh = jax.nn.one_hot(lab.astype(jnp.int32), n_cls, dtype=x.dtype)
        red = tuple(range(1, x.ndim))
        inse = jnp.sum(x * oh, axis=red)
        denom = jnp.sum(x, axis=red) + jnp.sum(oh, axis=red)
        return jnp.mean(1.0 - 2.0 * inse / (denom + epsilon))
    return apply(fn, input, label, op_name='dice_loss')


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """N-pair metric-learning loss (reference fluid/layers/loss.py
    npair_loss): soft-label CE over the anchor@positive.T similarity
    matrix + Beta*l2_reg embedding regularizer."""
    anchor = wrap(anchor)
    positive = wrap(positive)
    labels = wrap(labels)

    def fn(a, p, lab):
        beta = 0.25
        b = lab.shape[0]
        eq = (lab.reshape(b, 1) == lab.reshape(1, b)).astype(a.dtype)
        soft = eq / jnp.sum(eq, axis=1, keepdims=True)
        l2 = (jnp.mean(jnp.sum(a * a, axis=1)) +
              jnp.mean(jnp.sum(p * p, axis=1))) * beta * l2_reg
        sim = a @ p.T
        ce_rows = -jnp.sum(soft * jax.nn.log_softmax(sim, axis=-1),
                           axis=-1, keepdims=True)
        ce = jnp.mean(jnp.sum(soft * ce_rows, axis=0))
        return l2 + ce
    return apply(fn, anchor, positive, labels, op_name='npair_loss')


__all__ += ['dice_loss', 'npair_loss']
