"""The Pallas latent decode kernel
(ops/latent_attention.py:paged_decode_latent) against its
gather-and-attend reference, in Pallas interpret mode on the CPU:
lengths from one position to several fetch rounds, a row's independence
of its batch, nothing read past a length, the gate; the kernel compiled
at the published widths for a described v5e; and the flash forward at a
value width apart from the key width (latent attention expanded)."""
import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle  # noqa: F401
from paddle_tpu.ops import _gating
from paddle_tpu.ops import latent_attention as la

HEADS, BS, LATENT, ROPE, WIDTH = 8, 16, 128, 64, 8
NUM_BLOCKS = 64
# the pool's row: latent, rotary key, zeros to whole tiles
ROW = 256
SCALE = 192 ** -0.5
FULL = WIDTH * BS
# one, one under, at and one over a block, and past a fetch round of
# four blocks (the tests shrink the round)
LENGTHS = [1, BS - 1, BS, BS + 1, 5 * BS + 3, FULL]


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    # rounds of four blocks, so that a row takes several
    monkeypatch.setattr(la, 'ROUND_BYTES', 4 * BS * ROW * 4)
    yield


def _pool(dtype='float32', seed=0):
    rs = np.random.RandomState(seed)
    pool = rs.randn(NUM_BLOCKS, BS, ROW)
    pool[..., LATENT + ROPE:] = 0.0
    return jnp.asarray(pool, dtype)


def _rows(lengths, batch, seed=1):
    """Queries, tables and lengths: the first rows hold `lengths` on
    distinct blocks, the others sit on the trash block at length 1."""
    rs = np.random.RandomState(seed)
    tables = np.zeros((batch, WIDTH), np.int32)
    lens = np.ones((batch,), np.int32)
    free = iter(rs.permutation(np.arange(1, NUM_BLOCKS)))
    for i, n in enumerate(lengths):
        for b in range(-(-n // BS)):
            tables[i, b] = next(free)
        lens[i] = n
    q_lat = jnp.asarray(rs.randn(batch, HEADS, LATENT), jnp.float32)
    q_pe = jnp.asarray(rs.randn(batch, HEADS, ROPE), jnp.float32)
    return q_lat, q_pe, jnp.asarray(tables), jnp.asarray(lens)


def _kernel(q_lat, q_pe, pool, tables, lens):
    assert la.can_use_pallas_latent(pool, tables, HEADS, LATENT)
    return np.asarray(jax.jit(lambda *a: la.latent_attention(
        *a, SCALE))(q_lat, q_pe, pool, tables, lens))


def _reference(q_lat, q_pe, pool, tables, lens):
    q = la.to_row(jnp.concatenate([q_lat, q_pe], -1), ROW)
    return la._reference_latent(q, pool, tables, lens, LATENT, SCALE)


def _close(out, ref):
    ref = np.asarray(ref)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() <= 1e-5 * np.abs(ref).max()


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('length', LENGTHS)
def test_one_row_matches_the_reference(interpret_mode, dtype, length):
    pool = _pool(dtype)
    q_lat, q_pe, tables, lens = _rows([length], 1)
    assert la._blocks_a_round(pool, tables) == (4 if dtype == 'float32'
                                                else 8)
    _close(_kernel(q_lat, q_pe, pool, tables, lens),
           _reference(q_lat, q_pe, pool, tables, lens))


def test_a_ragged_batch_matches_the_reference(interpret_mode):
    pool = _pool()
    q_lat, q_pe, tables, lens = _rows(LENGTHS, 8)
    _close(_kernel(q_lat, q_pe, pool, tables, lens),
           _reference(q_lat, q_pe, pool, tables, lens))


@pytest.mark.parametrize('length', [1, BS + 1, 5 * BS + 3, FULL])
def test_a_row_does_not_depend_on_its_batch(interpret_mode, length):
    """Bitwise the same alone, in a batch of 8 and with the others
    permuted."""
    pool = _pool()
    others = [n for n in LENGTHS if n != length][:4]
    q_lat, q_pe, tables, lens = _rows([length] + others, 8)
    alone = _kernel(q_lat[:1], q_pe[:1], pool, tables[:1], lens[:1])[0]
    in8 = _kernel(q_lat, q_pe, pool, tables, lens)[0]
    order = np.array([3, 7, 0, 5, 1, 6, 2, 4])
    permuted = _kernel(q_lat[order], q_pe[order], pool, tables[order],
                       lens[order])[2]
    for got in (in8, permuted):
        np.testing.assert_array_equal(alone, got)


def test_nothing_past_a_length_reaches_the_result(interpret_mode):
    """NaN in the tail of each row's last block, in every block a table
    names past its length and in every block no table names."""
    pool = _pool()
    q_lat, q_pe, tables, lens = _rows(LENGTHS, 8)
    tables = np.array(tables)
    named = set(tables.ravel())
    spare = [b for b in range(1, NUM_BLOCKS) if b not in named]
    for i, n in enumerate(LENGTHS):
        for b in range(-(-n // BS), WIDTH):
            tables[i, b] = spare.pop()
    clean = _kernel(q_lat, q_pe, pool, jnp.asarray(tables), lens)
    poison = np.zeros((NUM_BLOCKS, BS), bool)
    poison[1:] = True
    for i, n in enumerate(LENGTHS):
        for b in range(-(-n // BS)):
            poison[tables[i, b]] = False
        if n % BS:
            poison[tables[i, n // BS], n % BS:] = True
    mask = jnp.asarray(poison)[:, :, None]
    out = _kernel(q_lat, q_pe, jnp.where(mask, jnp.nan, pool),
                  jnp.asarray(tables), lens)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(clean, out)


def test_write_latent_lands_at_its_block_and_offset():
    pool = _pool()
    tables = jnp.asarray([[3, 9, 0], [0, 0, 0]], jnp.int32)
    new = jnp.full((2, LATENT + ROPE), 7.0)
    pool2 = la.write_latent(pool, new, tables, jnp.asarray([BS + 2, 0]))
    assert (np.asarray(pool2[9, 2, :LATENT + ROPE]) == 7.0).all()
    assert (np.asarray(pool2[9, 2, LATENT + ROPE:]) == 0.0).all()
    # the second row writes the trash block; nothing else moved
    changed = np.argwhere(np.asarray((pool2 != pool).any(-1)))
    assert {tuple(x) for x in changed} == {(9, 2), (0, 0)}


class TestGate:
    def test_the_published_widths_take_the_kernel(self, interpret_mode,
                                                  monkeypatch):
        from paddle_tpu.ops import paged_attention as pa
        monkeypatch.setattr(la, 'ROUND_BYTES', pa.ROUND_BYTES)
        pool = jax.ShapeDtypeStruct((100, 16, 640), jnp.float32)
        tables = jax.ShapeDtypeStruct((48, 1152), jnp.int32)
        assert la.can_use_pallas_latent(pool, tables, 32, 512)

    def test_a_cpu_without_interpret_mode_takes_the_reference(self):
        _, _, tables, _ = _rows([BS], 8)
        assert not la.can_use_pallas_latent(_pool(), tables, HEADS, LATENT)

    @pytest.mark.parametrize('why, shape, heads, latent', [
        ('a latent of 96', (64, 16, 256), 32, 96),
        ('12 heads', (64, 16, 640), 12, 512),
        ('a block of 4 positions', (64, 4, 640), 32, 512),
        ('tables past SMEM', (64, 16, 640), 32, 512),
    ])
    def test_other_shapes_take_the_reference(self, interpret_mode, why,
                                             shape, heads, latent):
        pool = jax.ShapeDtypeStruct(shape, jnp.float32)
        rows = 256 if why == 'tables past SMEM' else 8
        tables = jax.ShapeDtypeStruct((rows, 1152), jnp.int32)
        assert not la.can_use_pallas_latent(pool, tables, heads,
                                            latent), why


# -- the flash forward at a value width apart from the key width ---------------
@pytest.mark.parametrize('t', [256, 1024])
def test_flash_forward_takes_values_narrower_than_keys(interpret_mode, t):
    """Latent attention expanded: keys of 192 (128 + the shared 64 of
    rotary), values of 128, causal, scale 192 ** -0.5, in interpret
    mode against the reference."""
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(4, t, 192), jnp.float32)
    k = jnp.asarray(rs.randn(4, t, 192), jnp.float32)
    v = jnp.asarray(rs.randn(4, t, 128), jnp.float32)
    assert fa.can_use_pallas(t, t, 192)
    out = fa.flash_attention(q, k, v, causal=True, scale=SCALE,
                             block_q=min(t, 512), block_k=min(t, 1024))
    ref = fa._reference(q, k, v, True, SCALE)
    assert out.shape == (4, t, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_forward_of_equal_widths_is_what_it_was(interpret_mode):
    """A caller whose values are as wide as its keys gets the kernel it
    always had: the same bits as its reference's tolerance allows, and
    gradients."""
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    rs = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rs.randn(2, 256, 128), jnp.float32)
               for _ in range(3))
    out = fa.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        fa._reference(q, k, v, True, 128 ** -0.5)), rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q: fa.flash_attention(q, k, v, causal=True).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


# -- at the published widths, for a described chip ------------------------------
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


def test_mosaic_compiles_the_latent_kernels_at_the_published_widths(
        one_chip):
    """joyai_llm_flash_serve, compiled, not run.  Its decode: 32 heads
    on the sublanes against a latent of 512 and a rotary key of 64,
    blocks of 16, 48 rows, tables of 1,152 (18,432 positions), rounds
    of 32 blocks.  Its prefill: 32 heads of keys 192 wide and values
    128 wide at 16,384 positions, tiles of (512, 1024), bfloat16."""
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    pool, tables = sd((24577, 16, 640), 'float32'), sd((48, 1152), 'int32')
    chunk = la._blocks_a_round(pool, tables)
    assert chunk == 25
    text = _uncached(lambda: la._paged_decode_latent.lower(
        sd((48, 32, 640), 'float32'), pool, tables, sd((48,), 'int32'),
        latent=512, scale=SCALE, chunk=chunk).compile()).as_text()
    assert 'tpu_custom_call' in text and 'paged_decode_latent' in text

    q = sd((32, 16384, 192), 'bfloat16')
    v = sd((32, 16384, 128), 'bfloat16')
    step = jax.jit(lambda q, k, v: fa._flash(q, k, v, True, SCALE, 512,
                                             1024))
    text = _uncached(lambda: step.lower(q, q, v).compile()).as_text()
    assert any('tpu_custom_call' in line and 'flash_fwd' in line
               for line in text.splitlines())
