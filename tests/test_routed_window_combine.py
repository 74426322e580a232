"""The grouped (prefill) expert product's combine in
`models/routed_window.py::_grouped`: the sort's inverse by a scatter and
each token's k results summed over a major k axis give the numbers the
un-sort by a second argsort and the [T, k, hidden] sum gave, bit for bit
on the CPU; compiled for a described v5e at SmallThinker's widths the
combine holds no [T, k, hidden] array and one sort."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import routed_window as rw

F32 = jnp.float32


def _grouped_before_the_combine(x, top_i, w, wg, wu, wd, active,
                                activation):
    """`routed_window._grouped` with its former combine, kept frozen:
    the un-sort by a second argsort and the sum over the k axis of a
    [T, k, hidden] reshape."""
    (T, k), E = top_i.shape, wg.shape[0]
    if active is not None:
        top_i = jnp.where(active[:, None], top_i, E)
    flat = top_i.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    rows = x[order // k]
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    if rw.grouped_path(T * k, wg, wd) == 'kernel':
        y = rw.gm.grouped_matmul(
            rw.gm.grouped_gate_up(rows, wg, wu, sizes, wd.dtype,
                                  activation), wd, sizes)
    else:
        g = jax.lax.ragged_dot(rows, wg, sizes, preferred_element_type=F32)
        u = jax.lax.ragged_dot(rows, wu, sizes, preferred_element_type=F32)
        y = jax.lax.ragged_dot(rw._epilogue(activation)(g, u, wd.dtype),
                               wd, sizes, preferred_element_type=F32)
    y = y[jnp.argsort(order)].reshape(T, k, -1)
    out = (y * w[:, :, None]).sum(1)
    if active is not None:
        out = jnp.where(active[:, None], out, 0.0)
    return out


def _operands(k, seed, T=48, E=12, hidden=64, width=32):
    """Rows, a choice and its weights as a router and a held range make
    them: k distinct experts a row, so the sort meets every expert id
    many times over; every fifth assignment to an expert held elsewhere
    (index E, weight 0); the last 9 rows pad (`active` false)."""
    rs = np.random.RandomState(seed)
    top_i = np.stack([rs.permutation(E)[:k] for _ in range(T)])
    elsewhere = rs.rand(T, k) < 0.2
    top_i = np.where(elsewhere, E, top_i).astype(np.int32)
    w = rs.rand(T, k).astype(np.float32)
    w = np.where(elsewhere, 0.0, w / w.sum(1, keepdims=True))
    active = np.arange(T) < T - 9
    x = rs.randn(T, hidden).astype(np.float32)
    wg, wu = (0.1 * rs.randn(E, hidden, width).astype(np.float32)
              for _ in range(2))
    wd = 0.1 * rs.randn(E, width, hidden).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (x, top_i, w, wg, wu, wd, active))


@pytest.mark.parametrize('k,activation', [(6, 'relu'), (8, 'silu')])
def test_the_combine_gives_the_numbers_of_the_second_sort(k, activation):
    """At k = 6 (SmallThinker) and k = 8 (Trinity-Mini, JoyAI), with
    pad rows, assignments to experts held elsewhere and every expert id
    tied many times in the stable sort: the same bits as the un-sort by
    a second argsort, and zeros in the pad rows."""
    x, top_i, w, wg, wu, wd, active = _operands(k, seed=k)
    assert int((top_i == wg.shape[0]).sum()) > 0
    now = jax.jit(rw._grouped, static_argnums=7)(
        x, top_i, w, wg, wu, wd, active, activation)
    before = jax.jit(_grouped_before_the_combine, static_argnums=7)(
        x, top_i, w, wg, wu, wd, active, activation)
    now, before = np.asarray(now), np.asarray(before)
    assert np.array_equal(now, before)
    assert not now[~np.asarray(active)].any()
    assert np.abs(now[np.asarray(active)]).sum(1).min() > 0


@pytest.mark.parametrize('k', [6, 8])
def test_the_scatter_inverts_the_stable_sort(k):
    """`_inverse(order)` is `argsort(order)` for the forward sort of a
    choice with pad rows, held-elsewhere assignments and tied ids."""
    _, top_i, *_, active = _operands(k, seed=10 + k)
    E = 12
    flat = jnp.where(active[:, None], top_i, E).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inv = rw._inverse(order)
    assert np.array_equal(np.asarray(inv), np.asarray(jnp.argsort(order)))
    assert np.array_equal(np.asarray(order[inv]), np.arange(flat.shape[0]))


# -- at SmallThinker's widths, for a described chip ------------------------------
@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


def _uncached(compile_fn):
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        return compile_fn()
    finally:
        jax.config.update('jax_enable_compilation_cache', cached)


def test_the_combine_compiles_with_no_relayout_and_one_sort(one_chip,
                                                            monkeypatch):
    """smallthinker_21b_serve's prefill bucket of 4,096 positions: six
    experts a position of 64, hidden 2,560, expert width 768, bfloat16
    weights, through the Pallas grouped product the chip takes.
    Compiled, not run: no f32[4096,6,2560] array (the k axis on the
    sublanes, padded 6 to 8), one sort (the forward one), and fewer
    bytes of temporaries than the second sort and the relayout took."""
    monkeypatch.setattr(rw, 'grouped_path', lambda *a: 'kernel')
    T, k, H, E, F = 4096, 6, 2560, 64, 768

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    args = (sd((T, H), 'bfloat16'), sd((T, k), 'int32'),
            sd((T, k), 'float32'), sd((E, H, F), 'bfloat16'),
            sd((E, H, F), 'bfloat16'), sd((E, F, H), 'bfloat16'),
            sd((T,), 'bool'))

    def compiled(fn):
        return _uncached(lambda: jax.jit(
            lambda *a: fn(*a, 'relu')).lower(*args).compile())

    now, before = compiled(rw._grouped), compiled(_grouped_before_the_combine)
    text, text_before = now.as_text(), before.as_text()
    for t in (text, text_before):
        assert 'grouped_gate_up' in t and 'grouped_matmul' in t
    assert f'f32[{T},{k},{H}]' in text_before
    assert f'f32[{T},{k},{H}]' not in text
    sorts = [line for line in text.splitlines() if ' sort(' in line]
    assert len(sorts) == 1, sorts
    assert text_before.count(' sort(') == 2
    assert (now.memory_analysis().temp_size_in_bytes
            < before.memory_analysis().temp_size_in_bytes)
