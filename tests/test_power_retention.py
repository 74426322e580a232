"""ops/power_retention.py against the three forms of the layer's
mathematics (ISSUE 27, section 1), on the CPU at tiny widths: the
masked square (benchmark/reference/brumby_ref.py), the recurrence with
the textbook symmetric square written out here in numpy, and the
op's chunked prefill and one-token decode; padding, a state to start
from, the feature map's layout, and the Pallas decode kernel in
interpret mode against the plain update.

Tolerances: everything here is float32 on the CPU; the forms differ by
the order of a few thousand additions, so they agree to 2e-5 of the
output's range (measured: 1e-6 to 4e-6).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle  # noqa: F401
from paddle_tpu.ops import _gating
from paddle_tpu.ops import power_retention as pr
from benchmark.reference import brumby_ref

T, HQ, HKV, D_HEAD = 24, 4, 2, 16
EPS, THETA = 1e-6, 10000.0
TOL = 2e-5


def _close(out, ref, tol=TOL):
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() <= tol * max(np.abs(ref).max(), 1.0)


def _inputs(seed=0, t=T, d=D_HEAD):
    rs = np.random.RandomState(seed)
    q = rs.randn(t, HQ * d).astype(np.float32)
    k = rs.randn(t, HKV * d).astype(np.float32)
    v = rs.randn(t, HKV * d).astype(np.float32)
    graw = (2.0 + rs.randn(t, HKV)).astype(np.float32)
    qn = (1.0 + 0.1 * rs.randn(d)).astype(np.float32)
    kn = (1.0 + 0.1 * rs.randn(d)).astype(np.float32)
    return q, k, v, graw, qn, kn


def _heads(q, k, v, graw, qn, kn, d=D_HEAD):
    """What the reference's square sees inside: normed and rotated
    q [T,Hq,d], k [T,Hkv,d], v [T,Hkv,d], log decay g [T,Hkv]."""
    t = q.shape[0]
    qp = brumby_ref._rope(brumby_ref._rms(
        jnp.asarray(q).reshape(t, HQ, d), qn, EPS), THETA)
    kp = brumby_ref._rope(brumby_ref._rms(
        jnp.asarray(k).reshape(t, HKV, d), kn, EPS), THETA)
    return (np.asarray(qp), np.asarray(kp), v.reshape(t, HKV, d),
            np.asarray(jax.nn.log_sigmoid(jnp.asarray(graw))))


def _square(raw):
    q, k, v, graw, qn, kn = raw
    return np.asarray(brumby_ref._retention(
        q, k, v, graw, qn, kn, heads=HQ, kv_heads=HKV, eps=EPS,
        theta=THETA)).reshape(q.shape[0], HQ, -1)


def _textbook_phi(x):
    """x_a^2 for each a, sqrt(2) x_a x_b for a < b."""
    d = x.shape[-1]
    iu = np.triu_indices(d, 1)
    return np.concatenate([x * x, np.sqrt(2.0) * x[..., iu[0]]
                           * x[..., iu[1]]], -1)


def _recurrence(qp, kp, v, g):
    """The second form, a token at a time, float64."""
    t, _, d = qp.shape
    group = HQ // HKV
    D = d * (d + 1) // 2
    S = np.zeros((HKV, D, d))
    z = np.zeros((HKV, D))
    y = np.zeros((t, HQ, d))
    for i in range(t):
        for j in range(HKV):
            fk = _textbook_phi(kp[i, j].astype(np.float64))
            S[j] = np.exp(g[i, j]) * S[j] + np.outer(fk, v[i, j])
            z[j] = np.exp(g[i, j]) * z[j] + fk
            for h in range(j * group, (j + 1) * group):
                fq = _textbook_phi(qp[i, h].astype(np.float64)) / d
                y[i, h] = fq @ S[j] / (fq @ z[j] + pr.EPS_R)
    return y


def test_the_three_forms_agree():
    raw = _inputs()
    qp, kp, v, g = _heads(*raw)
    square = _square(raw)
    _close(_recurrence(qp, kp, v, g), square)
    for chunk in (T, 8, 5):             # one chunk, three, ragged
        y, _ = pr.retention_prefill(
            qp[None], kp[None], v[None], g[None], jnp.asarray([T]),
            chunk=chunk)
        _close(y[0], square)


def test_the_feature_map_is_the_symmetric_square_in_another_layout():
    rs = np.random.RandomState(1)
    for d in (16, 128):
        q, k = rs.randn(3, d), rs.randn(3, d)
        fq, fk = pr.phi(jnp.asarray(q)), pr.phi(jnp.asarray(k))
        assert fq.shape == (3, pr.num_features(d))
        assert pr.num_features(d) == d * (d + 1) // 2 + d // 2
        _close((fq * fk).sum(-1), (q * k).sum(-1) ** 2, 1e-5)
        _close((fq * fk).sum(-1),
               (_textbook_phi(q) * _textbook_phi(k)).sum(-1), 1e-5)


def _decode_all(qp, kp, v, g, slots=3, slot=1):
    """The op's one-token form from the empty state, in `slot`."""
    d = qp.shape[-1]
    S = jnp.zeros((slots, HKV, d, pr.num_features(d)), jnp.float32)
    z = jnp.zeros((slots, HKV, pr.num_features(d)), jnp.float32)
    step = jax.jit(pr.retention_decode)
    ys = []
    for i in range(qp.shape[0]):
        y, S, z = step(qp[i][None], kp[i][None], v[i][None], g[i][None],
                       S, z, jnp.asarray([slot]), jnp.asarray([True]))
        ys.append(np.asarray(y[0]))
    return np.stack(ys), S, z


def test_decoding_token_by_token_is_the_square_and_ends_in_prefills_state():
    raw = _inputs(seed=2)
    qp, kp, v, g = _heads(*raw)
    y, S, z = _decode_all(qp, kp, v, g)
    _close(y, _square(raw))
    _, (S_p, z_p) = pr.retention_prefill(
        qp[None], kp[None], v[None], g[None], jnp.asarray([T]), chunk=8)
    _close(S[1], S_p[0])
    _close(z[1], z_p[0])
    assert not np.asarray(S[0]).any() and not np.asarray(S[2]).any()


@pytest.mark.parametrize('length', [1, 7, 8, 9, 19])
def test_pad_positions_reach_neither_the_output_nor_the_state(length):
    """A row padded to the bucket gives what the row alone gives: the
    state at its TRUE length, pads adding neither decay nor phi(k) v."""
    raw = _inputs(seed=3)
    qp, kp, v, g = _heads(*raw)
    args = [x[None] for x in (qp, kp, v, g)]
    y, (S, z) = pr.retention_prefill(*args, jnp.asarray([length]),
                                     chunk=8)
    cut = [x[:, :length] for x in args]
    y0, (S0, z0) = pr.retention_prefill(*cut, jnp.asarray([length]),
                                        chunk=8)
    _close(y[0, :length], y0[0])
    _close(S, S0)
    _close(z, z0)
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize('chunk', [4, 8, 16])
def test_a_prompt_in_several_chunks_is_the_prompt_in_one(chunk):
    """The state a chunk hands the next (its own decayed, plus what the
    chunk added) and what it adds to the later outputs: the scan over
    chunks gives what one chunk over the whole prompt gives."""
    raw = _inputs(seed=4)
    qp, kp, v, g = _heads(*raw)
    args = [x[None] for x in (qp, kp, v, g)]
    whole, (S, z) = pr.retention_prefill(*args, jnp.asarray([T - 3]),
                                         chunk=T)
    y, (S2, z2) = pr.retention_prefill(*args, jnp.asarray([T - 3]),
                                       chunk=chunk)
    _close(y[0, :T - 3], whole[0, :T - 3])
    _close(S2, S)
    _close(z2, z)


def test_two_rows_of_a_batch_do_not_meet():
    a, b = _heads(*_inputs(seed=5)), _heads(*_inputs(seed=6))
    both = [jnp.stack([x, y]) for x, y in zip(a, b)]
    lengths = jnp.asarray([T, 13])
    y, (S, _z) = pr.retention_prefill(*both, lengths, chunk=8)
    ya, (Sa, _) = pr.retention_prefill(*[x[None] for x in a],
                                       lengths[:1], chunk=8)
    yb, (Sb, _) = pr.retention_prefill(*[x[None] for x in b],
                                       lengths[1:], chunk=8)
    _close(y[0], ya[0])
    _close(y[1, :13], yb[0, :13])
    _close(S[0], Sa[0])
    _close(S[1], Sb[0])


# -- the Pallas decode kernel, interpret mode ----------------------------------
@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    yield


def _decode_operands(rows, slots, seed=0, d=128, hq=4, hkv=2):
    rs = np.random.RandomState(seed)
    D = pr.num_features(d)
    return (jnp.asarray(rs.randn(rows, hq, d), jnp.float32),
            jnp.asarray(rs.randn(rows, hkv, d), jnp.float32),
            jnp.asarray(rs.randn(rows, hkv, d), jnp.float32),
            jnp.asarray(-np.abs(rs.randn(rows, hkv)) * 0.3, jnp.float32),
            jnp.asarray(rs.randn(slots, hkv, d, D), jnp.float32),
            jnp.asarray(np.abs(rs.randn(slots, hkv, D)) + 1.0,
                        jnp.float32))


def _step(*operands):
    """retention_decode under a jit of its own: the gate is asked when
    the function is traced."""
    return jax.jit(lambda *a: pr.retention_decode(*a))(*operands)


def test_the_kernel_matches_the_plain_update(monkeypatch):
    q, k, v, g, S, z = _decode_operands(3, 5)
    slots = jnp.asarray([3, 0, 4], jnp.int32)
    active = jnp.asarray([True, False, True])
    assert not pr.can_use_pallas(S, q)          # a CPU: the plain path
    y0, S0, z0 = _step(q, k, v, g, S, z, slots, active)
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    assert pr.can_use_pallas(S, q)
    y1, S1, z1 = _step(q, k, v, g, S, z, slots, active)
    live = np.asarray(active)
    # 8,320 products a read-out in another order: 1e-5 of the range
    _close(np.asarray(y1)[live], np.asarray(y0)[live], 1e-5)
    _close(S1, S0, 1e-6)
    _close(z1, z0, 1e-6)
    # slots nobody named, and the inactive row's, are bitwise as before
    for s in (0, 1, 2):
        assert np.array_equal(np.asarray(S1[s]), np.asarray(S[s]))


def test_a_row_of_the_kernel_does_not_depend_on_its_batch(interpret_mode):
    q, k, v, g, S, z = _decode_operands(4, 6, seed=1)
    slots = jnp.asarray([5, 2, 0, 3], jnp.int32)
    active = jnp.ones((4,), bool)
    y, S_all, _ = _step(q, k, v, g, S, z, slots, active)
    for r in (0, 3):
        y1, S1, _ = _step(q[r:r + 1], k[r:r + 1], v[r:r + 1], g[r:r + 1],
                          S, z, slots[r:r + 1], active[:1])
        assert np.array_equal(np.asarray(y1[0]), np.asarray(y[r]))
        s = int(slots[r])
        assert np.array_equal(np.asarray(S1[s]), np.asarray(S_all[s]))


class TestTheGate:
    def test_other_shapes_and_dtypes_take_the_plain_path(
            self, interpret_mode):
        q, _k, _v, _g, S, _z = _decode_operands(2, 2)
        assert pr.can_use_pallas(S, q)
        assert not pr.can_use_pallas(S.astype(jnp.bfloat16), q)
        q16 = jnp.zeros((2, 4, 16), jnp.float32)
        S16 = jnp.zeros((2, 2, 16, pr.num_features(16)), jnp.float32)
        assert not pr.can_use_pallas(S16, q16)

    def test_a_mesh_takes_the_plain_path(self, interpret_mode):
        from jax.sharding import Mesh
        from paddle_tpu.distributed import env as dist_env
        q, _k, _v, _g, S, _z = _decode_operands(2, 2)
        dist_env.set_mesh(Mesh(np.asarray(jax.devices()[:2]), ('tp',)))
        try:
            assert not pr.can_use_pallas(S, q)
        finally:
            dist_env.set_mesh(None)
