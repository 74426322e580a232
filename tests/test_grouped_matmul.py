"""The Pallas grouped matmul of the routed prefill (PR 34,
`ops/grouped_matmul.py`) in interpret mode on the CPU: each property
one parametrised test.  On-chip speed is the benchmark's
`moe_prefill_roofline.moe_window`; that Mosaic takes the kernel at the
cell's widths is compiled in `test_paged_attention_kernel.py`."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models.routed_window import _gated, _gated_silu
from paddle_tpu.ops import _gating
from paddle_tpu.ops import grouped_matmul as gm

M, K, N, E = 1024, 256, 128, 8

# sizes of the eight groups over 1,024 rows, by what they exercise
SIZES = {
    'an empty group and groups that end mid-tile':
        [100, 0, 300, 28, 84, 1, 255, 256],
    'a group smaller than a tile, alone in its tile': [128, 7, 121] + [0] * 5,
    'all rows in one group': [0, 0, M, 0, 0, 0, 0, 0],
    'every group a whole tile': [128] * 8,
    'rows behind the last group': [100, 0, 300, 28, 84, 1, 55, 0],
    'most tiles reached by no group': [5] * 8,
    'no group holds a row': [0] * 8,
}


@pytest.fixture(scope='module')
def operands():
    rs = np.random.RandomState(0)
    return (jnp.asarray(rs.randn(M, K), jnp.bfloat16),
            jnp.asarray(rs.randn(E, K, N) * .1, jnp.bfloat16),
            jnp.asarray(rs.randn(E, K, N) * .1, jnp.bfloat16))


def _plain(rows, w, sizes, tm):
    return gm._grouped(rows, (w,), jnp.asarray(sizes, jnp.int32), tm=tm,
                       dtype=jnp.float32, interpret=True)


def _loop(rows, w, sizes):
    """Each group's rows against its matrix, one group at a time;
    zeros behind the last group."""
    out = np.zeros((rows.shape[0], w.shape[2]), np.float32)
    ends = np.cumsum(sizes)
    for g, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        out[lo:hi] = np.asarray(jnp.dot(rows[lo:hi], w[g],
                                        preferred_element_type=jnp.float32))
    return out


@pytest.mark.parametrize('tm', [128, 256])
@pytest.mark.parametrize('what', list(SIZES))
def test_matches_a_loop_over_the_groups(operands, what, tm):
    rows, w, _ = operands
    sizes = np.asarray(SIZES[what])
    got = np.asarray(_plain(rows, w, sizes, tm))
    want = _loop(rows, w, sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not got[sizes.sum():].any()        # no group's rows: zeros


@pytest.mark.parametrize('tm', [128, 256])
@pytest.mark.parametrize('what', list(SIZES))
def test_gate_and_up_in_one_pass_is_gated_of_two_calls_bitwise(
        operands, what, tm):
    rows, wg, wu = operands
    sizes = jnp.asarray(SIZES[what], jnp.int32)
    fused = gm._grouped(rows, (wg, wu), sizes, tm=tm, dtype=jnp.bfloat16,
                        interpret=True)
    two = _gated(_plain(rows, wg, sizes, tm), _plain(rows, wu, sizes, tm),
                 jnp.bfloat16)
    assert fused.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(fused, np.float32),
                          np.asarray(two, np.float32))


def test_silu_at_128_groups_of_fewer_rows_than_a_tile():
    """The routed decoder with 128 SwiGLU experts (PR 35): a prompt of
    1,024 at 8 experts a token gives an expert some 64 rows, half a
    tile, so nearly every tile is shared by two or three groups.  The
    one pass with `activation='silu'` against `jax.lax.ragged_dot` and
    XLA's epilogue, and bit for bit against two plain calls."""
    rs = np.random.RandomState(1)
    m, k, n, e = 2048, 128, 128, 128
    sizes = rs.randint(0, 32, e)
    sizes[5], sizes[77] = 0, 127
    assert sizes.sum() <= m and (sizes < gm.TILE_ROWS).all()
    rows = jnp.asarray(rs.randn(m, k), jnp.bfloat16)
    wg, wu = (jnp.asarray(rs.randn(e, k, n) * .1, jnp.bfloat16)
              for _ in range(2))
    sizes = jnp.asarray(sizes, jnp.int32)
    fused = gm._grouped(rows, (wg, wu), sizes, tm=gm.TILE_ROWS,
                        dtype=jnp.bfloat16, activation='silu',
                        interpret=True)
    two = _gated_silu(_plain(rows, wg, sizes, gm.TILE_ROWS),
                      _plain(rows, wu, sizes, gm.TILE_ROWS), jnp.bfloat16)
    assert np.array_equal(np.asarray(fused, np.float32),
                          np.asarray(two, np.float32))
    relu = gm._grouped(rows, (wg, wu), sizes, tm=gm.TILE_ROWS,
                       dtype=jnp.bfloat16, interpret=True)
    assert not np.array_equal(np.asarray(fused, np.float32),
                              np.asarray(relu, np.float32))
    held = int(sizes.sum())
    want = _gated_silu(*(jax.lax.ragged_dot(
        rows, w, sizes, preferred_element_type=jnp.float32)
        for w in (wg, wu)), jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(fused, np.float32)[:held],
        np.asarray(want, np.float32)[:held], rtol=2e-2, atol=1e-3)
    assert not np.asarray(fused, np.float32)[held:].any()


@pytest.mark.parametrize('tm', [128, 256])
@pytest.mark.parametrize('what', list(SIZES))
def test_the_visits_fit_the_grid_and_cover_each_group_once(what, tm):
    sizes = np.asarray(SIZES[what])
    offsets, group, tile, src, real = (
        np.asarray(a) for a in gm.group_metadata(
            jnp.asarray(sizes, jnp.int32), M, tm))
    real = int(real[0])
    assert group.shape == (M // tm + E - 1,)        # the grid's length
    assert real <= -(-M // tm) + E - 1
    assert list(offsets) == [0] + list(np.cumsum(sizes))
    rows_of = np.zeros(E, np.int64)
    for g, t in zip(group[:real], tile[:real]):
        rows_of[g] += max(0, min(offsets[g + 1], (t + 1) * tm)
                          - max(offsets[g], t * tm))
    assert list(rows_of) == list(sizes)
    assert len(set(zip(group[:real], tile[:real]))) == real
    # behind them: the tiles no group reaches, once each, then nothing
    reached = -(-int(sizes.sum()) // tm)
    behind = tile[real:real + M // tm - reached]
    assert list(behind) == list(range(reached, M // tm))
    assert (tile[real + len(behind):] == M // tm - 1).all()
    # and nothing is fetched for them
    assert len(set(src[max(real - 1, 0):])) == 1
    assert len(set(group[max(real - 1, 0):])) == 1
    assert (np.diff(tile) >= 0).all() and tile.max() < M // tm


@pytest.mark.parametrize('tm', [128, 256])
def test_a_rows_result_does_not_depend_on_the_rows_in_its_tile(operands,
                                                               tm):
    """What the routed layer promises ("a pad row moves no other row")
    holds through the kernel: other rows of the tile, of the same
    group or the next, change nothing, bit for bit."""
    rows, w, _ = operands
    sizes = np.asarray(SIZES['an empty group and groups that end mid-tile'])
    base = np.asarray(_plain(rows, w, sizes, tm))
    other = np.asarray(rows, np.float32).copy()
    moved = [3, 99, 100, 101, 399, 400, 1023]       # both sides of two ends
    other[moved] = 100.0
    got = np.asarray(_plain(jnp.asarray(other, jnp.bfloat16), w, sizes, tm))
    keep = np.setdiff1d(np.arange(M), moved)
    assert np.array_equal(got[keep], base[keep])
    assert not np.array_equal(got[moved], base[moved])


@pytest.mark.parametrize('why,m,shape,dtype,interpret,matrices,want', [
    ('the cell: gate and up', 73728, (64, 2560, 768), 'bfloat16', True, 2,
     True),
    ('the cell: down', 6144, (64, 768, 2560), 'bfloat16', True, 1, True),
    ('a CPU without interpret mode', 6144, (64, 2560, 768), 'bfloat16',
     False, 1, False),
    ('float32 weights', 6144, (64, 2560, 768), 'float32', True, 1, False),
    ('a width that is no whole vreg', 384, (8, 64, 32), 'bfloat16', True, 1,
     False),
    ('rows no tile divides', 72, (8, 128, 128), 'bfloat16', True, 1, False),
    ('matrices VMEM cannot hold twice', 6144, (8, 4096, 4096), 'bfloat16',
     True, 2, False),
    ('a matrix of rank 2', 6144, (2560, 768), 'bfloat16', True, 1, False),
])
def test_the_gate(monkeypatch, why, m, shape, dtype, interpret, matrices,
                  want):
    monkeypatch.setattr(_gating, 'INTERPRET', interpret)
    w = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    assert gm.can_use_pallas(m, w, matrices) is want, why


def test_the_entry_points_take_the_tile_and_the_mode(monkeypatch, operands):
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    rows, wg, wu = operands
    sizes = jnp.asarray(SIZES['rows behind the last group'], jnp.int32)
    assert np.array_equal(
        np.asarray(gm.grouped_matmul(rows, wg, sizes)),
        np.asarray(_plain(rows, wg, sizes, gm.TILE_ROWS)))
    a = gm.grouped_gate_up(rows, wg, wu, sizes, jnp.bfloat16)
    assert a.dtype == jnp.bfloat16 and a.shape == (M, N)
