"""`ops/ssm.py` against the definition (the sequential recurrence of
benchmark/reference/granite_ref.py, a position at a time): the chunked
prefill over ragged rows at chunks of 4 and 8, the one-token update on
its plain path and through the Pallas kernel in interpret mode, the
causal conv and the conv state it keeps, and what an inactive or a
padding row leaves behind."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle  # noqa: F401
from paddle_tpu.ops import _gating, ssm
from benchmark.reference import granite_ref

H, P, N = 8, 64, 16        # H * P = 512: one piece of the kernel's lanes


@pytest.fixture(autouse=True)
def no_mesh():
    """One device and no mesh, whatever a test before this file left
    set: the kernels' gate takes no mesh."""
    from paddle_tpu.distributed import env as dist_env
    before = dist_env.get_mesh()
    dist_env.set_mesh(None)
    yield
    dist_env.set_mesh(before)


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    yield


def _inputs(B, T, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(B, T, H, P).astype('f4')
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(1e-1), (B, T, H)))
    A = -np.arange(1, H + 1, dtype='f4')
    Bm, Cm = rs.randn(B, T, N).astype('f4'), rs.randn(B, T, N).astype('f4')
    return x, dt.astype('f4'), A, Bm, Cm


def _definition(x, dt, A, Bm, Cm):
    """One row's y [T, H, P] and final state [H, P, N]."""
    with jax.default_matmul_precision('highest'):
        y, S = granite_ref.recurrence(*(jnp.asarray(v) for v in
                                        (x, dt, A, Bm, Cm)))
    return np.asarray(y), np.asarray(S)


@pytest.mark.parametrize('chunk', [4, 8])
def test_the_chunked_prefill_is_the_recurrence_over_ragged_rows(chunk):
    """Rows of 13, 6 and 1 true positions in a bucket of 13: a pad (dt
    0) neither decays nor feeds, so each row's state is the one at its
    true length, in the cache's layout."""
    x, dt, A, Bm, Cm = _inputs(3, 13)
    lengths = np.array([13, 6, 1])
    valid = np.arange(13)[None, :] < lengths[:, None]
    y, S = jax.jit(lambda *a: ssm.ssd_prefill(*a, chunk=chunk))(
        x, np.where(valid[..., None], dt, 0.0), A, Bm, Cm)
    assert S.shape == (3, N, H * P)
    for b, n in enumerate(lengths):
        want_y, want_S = _definition(x[b, :n], dt[b, :n], A, Bm[b, :n],
                                     Cm[b, :n])
        np.testing.assert_allclose(np.asarray(y)[b, :n], want_y,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(ssm.heads_of(S[b], H)),
                                   want_S, rtol=1e-4, atol=1e-5)


def _decode_case(seed=1):
    """Six slots, four rows: three live at their own slots, one padding
    row on a slot nobody holds; the slots hold a state each."""
    rs = np.random.RandomState(seed)
    S = rs.randn(6, N, H * P).astype('f4')
    x = rs.randn(4, H, P).astype('f4')
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(1e-1), (4, H))).astype('f4')
    A = -np.arange(1, H + 1, dtype='f4')
    Bm, Cm = rs.randn(4, N).astype('f4'), rs.randn(4, N).astype('f4')
    slots = np.array([4, 0, 2, 5], np.int32)
    active = np.array([True, True, False, True])
    return x, dt, A, Bm, Cm, S, slots, active


def _decode_by_hand(x, dt, A, Bm, Cm, S, slots, active):
    S = np.asarray(S, np.float64).copy()
    y = np.zeros(x.shape)
    for r, s in enumerate(slots):
        if not active[r]:
            continue
        St = ssm.heads_of(jnp.asarray(S[s]), H)           # [H, P, N]
        St = np.exp(dt[r] * A)[:, None, None] * np.asarray(St) \
            + (dt[r][:, None] * x[r])[..., None] * Bm[r][None, None]
        y[r] = St @ Cm[r]
        S[s] = np.moveaxis(St, -1, 0).reshape(N, H * P)
    return y, S


@pytest.mark.parametrize('path', ['plain', 'kernel'])
def test_the_update_is_one_step_of_the_recurrence(monkeypatch, path):
    """Each live row's slot takes one step of the definition and its y
    is the state read by C; the inactive row leaves its slot as it was;
    slots no row names are untouched."""
    monkeypatch.setattr(_gating, 'INTERPRET', path == 'kernel')
    case = _decode_case()
    assert ssm.can_use_pallas(jnp.asarray(case[5])) == (path == 'kernel')
    y, S = jax.jit(ssm.ssm_decode)(*case)
    want_y, want_S = _decode_by_hand(*case)
    live = case[-1]
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), want_S, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(S)[[1, 2, 3]],
                                  case[5][[1, 2, 3]])


def test_prefill_then_decode_is_the_recurrence(interpret_mode):
    """A prompt's state from the chunked form, then two tokens through
    the kernel, against the definition over the whole sequence."""
    x, dt, A, Bm, Cm = _inputs(1, 11, seed=4)
    _, S = ssm.ssd_prefill(x[:, :9], dt[:, :9], A, Bm[:, :9], Cm[:, :9],
                           chunk=4)
    slots = np.zeros((2, N, H * P), 'f4')
    slots[1] = S[0]
    state = jnp.asarray(slots)
    for t in (9, 10):
        y, state = ssm.ssm_decode(
            np.stack([x[0, t]] * 2), np.stack([dt[0, t]] * 2), A,
            np.stack([Bm[0, t]] * 2), np.stack([Cm[0, t]] * 2), state,
            np.array([1, 0], np.int32), np.array([True, False]))
    want_y, want_S = _definition(x[0], dt[0], A, Bm[0], Cm[0])
    np.testing.assert_allclose(np.asarray(y)[0], want_y[-1], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ssm.heads_of(state[1], H)),
                               want_S, rtol=1e-4, atol=1e-5)


def test_the_conv_keeps_the_last_true_inputs():
    """The conv state of a row is its inputs at its last three TRUE
    positions, zeros before position 0; a token's step against it gives
    what the whole conv gives at that position."""
    rs = np.random.RandomState(7)
    C = 24
    x = rs.randn(3, 10, C).astype('f4')
    w, b = rs.randn(4, C).astype('f4'), rs.randn(C).astype('f4')
    lengths = np.array([10, 5, 2])
    y, state = ssm.causal_conv1d(x, w, b, jnp.asarray(lengths))
    for r, n in enumerate(lengths):
        want = np.zeros((3, C), 'f4')
        take = x[r, max(0, n - 3):n]
        want[3 - take.shape[0]:] = take
        np.testing.assert_array_equal(np.asarray(state)[r], want)
    # a step at position 4 of row 0 from its state after 4 positions
    _, kept = ssm.causal_conv1d(x[:1, :4], w, b, jnp.asarray([4]))
    step, new = ssm.conv_step(x[:1, 4], kept, w, b)
    np.testing.assert_allclose(np.asarray(step)[0], np.asarray(y)[0, 4],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new)[0], x[0, 2:5])


def test_fast_heads_keep_their_recent_weights_over_a_long_chunk():
    """Heads of A down to -64 with the model's spread of dt (softplus of
    a bias and a draw) over chunks of 256: a chunk's log decays sum to
    some thousands, and a decay taken as the difference of two running
    sums keeps float32's share of them (4.6e-5 here), some forty times
    under what the activations' bfloat16 rounding puts in a layer's
    output (`mamba_rel` 2e-3 on the chip); a chunk misplaced or a decay
    mis-summed reads far over."""
    rs = np.random.RandomState(0)
    heads, T = 64, 600
    x = rs.randn(1, T, heads, 4).astype('f4')
    Bm, Cm = rs.randn(1, T, 8).astype('f4'), rs.randn(1, T, 8).astype('f4')
    dt = np.log1p(np.exp(rs.uniform(-7, 0, (1, T, heads))
                         + rs.randn(1, T, heads))).astype('f4')
    A = -np.arange(1, heads + 1, dtype='f4')
    _, S = jax.jit(lambda *a: ssm.ssd_prefill(*a, chunk=256))(
        x, dt, A, Bm, Cm)
    _, want = _definition(x[0], dt[0], A, Bm[0], Cm[0])
    got = np.asarray(ssm.heads_of(S[0], heads))
    err = np.sqrt(((got - want) ** 2).sum((1, 2)) / (want ** 2).sum((1, 2)))
    assert err.max() < 1e-4, err.max()
