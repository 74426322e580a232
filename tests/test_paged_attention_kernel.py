"""The Pallas paged decode kernel (ops/paged_attention.py:paged_decode)
against the gather-and-attend reference, in Pallas interpret mode on
the CPU: ragged lengths, both pool dtypes, every batch bucket, a row's
independence of its batch, nothing read past a length, the gate, and
the kernel compiled at the serving configuration's widths for a
described v5e.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as paddle  # noqa: F401
from paddle_tpu.ops import _gating
from paddle_tpu.ops import paged_attention as pa

# heads and positions differ, so a pool in the other order is another
# shape (at the serving widths both are 16: the compile tests below)
NH, BS, HD, WIDTH = 16, 8, 128, 6
NUM_BLOCKS = 48
FULL = WIDTH * BS
# 1, one under, at and one over a block boundary, mid-table, the table
LENGTHS = [1, BS - 1, BS, BS + 1, 3 * BS + 5, FULL]


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    yield


def _pools(dtype, seed=0, num_blocks=NUM_BLOCKS, hd=HD):
    rs = np.random.RandomState(seed)
    shape = (num_blocks, BS, NH, hd)
    return (jnp.asarray(rs.randn(*shape), dtype),
            jnp.asarray(rs.randn(*shape), dtype))


def _rows(lengths, batch, seed=1, hd=HD):
    """q, tables and lens for `batch` slots: the first rows hold
    `lengths` on distinct blocks, the rest sit on the trash block with
    length 1, as the scheduler pads a plan."""
    rs = np.random.RandomState(seed)
    tables = np.zeros((batch, WIDTH), np.int32)
    lens = np.ones((batch,), np.int32)
    free = iter(rs.permutation(np.arange(1, NUM_BLOCKS)))
    for i, n in enumerate(lengths):
        for b in range(-(-n // BS)):
            tables[i, b] = next(free)
        lens[i] = n
    q = jnp.asarray(rs.randn(batch, NH, hd), jnp.float32)
    return q, jnp.asarray(tables), jnp.asarray(lens)


def _attend(*operands):
    """paged_attention under a jit of its own: jax keys a trace by the
    function, and the gate is asked when the function is traced."""
    return jax.jit(lambda *a: pa.paged_attention(*a))(*operands)


def _kernel(q, kp, vp, tables, lens):
    assert pa.can_use_pallas(kp, tables)
    return np.asarray(_attend(q, kp, vp, tables, lens))


def _close(out, ref):
    ref = np.asarray(ref)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() <= 1e-5 * np.abs(ref).max()


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('length', LENGTHS)
def test_one_row_matches_the_reference(interpret_mode, dtype, length):
    kp, vp = _pools(dtype)
    q, tables, lens = _rows([length], 1)
    _close(_kernel(q, kp, vp, tables, lens),
           pa._reference(q, kp, vp, tables, lens))


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('batch', [8, 32])
def test_a_ragged_batch_matches_the_reference(interpret_mode, dtype,
                                              batch):
    """Every length at once, the other slots inactive on the trash
    block; the output has the reference's dtype."""
    kp, vp = _pools(dtype)
    q, tables, lens = _rows(LENGTHS, batch)
    out = _attend(q, kp, vp, tables, lens)
    ref = pa._reference(q, kp, vp, tables, lens)
    assert out.dtype == ref.dtype and out.shape == ref.shape
    _close(np.asarray(out), ref)


@pytest.mark.parametrize('blocks_a_round', [1, 4])
def test_the_fetch_round_does_not_change_the_result(
        interpret_mode, monkeypatch, blocks_a_round):
    """Rounds of one block and rounds that do not divide the table."""
    monkeypatch.setattr(pa, 'ROUND_BYTES',
                        blocks_a_round * NH * BS * HD * 4)
    kp, vp = _pools('float32')
    q, tables, lens = _rows(LENGTHS, 8)
    _close(_kernel(q, kp, vp, tables, lens),
           pa._reference(q, kp, vp, tables, lens))


@pytest.mark.parametrize('length', [1, BS + 1, 3 * BS + 5, FULL])
def test_a_row_does_not_depend_on_its_batch(interpret_mode, length):
    """Bitwise the same alone, in a batch of 8, in a batch of 32 and
    with the other rows permuted: batch composition cannot perturb a
    request's stream (serving/engine.py's promise)."""
    kp, vp = _pools('float32')
    others = [n for n in LENGTHS if n != length][:4]
    q, tables, lens = _rows([length] + others, 32)
    alone = _kernel(q[:1], kp, vp, tables[:1], lens[:1])[0]
    in8 = _kernel(q[:8], kp, vp, tables[:8], lens[:8])[0]
    in32 = _kernel(q, kp, vp, tables, lens)[0]
    order = np.array([3, 7, 0, 5, 1, 6, 2, 4])
    permuted = _kernel(q[order], kp, vp, tables[order], lens[order])[2]
    for got in (in8, in32, permuted):
        np.testing.assert_array_equal(alone, got)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_nothing_past_a_length_reaches_the_result(interpret_mode, dtype):
    """NaN in every block a table names past its row's length, in the
    tail of each row's last block and in every block no table names:
    the output is finite and bitwise what it was."""
    kp, vp = _pools(dtype)
    q, tables, lens = _rows(LENGTHS, 8)
    tables = np.array(tables)
    named = set(tables.ravel())
    spare = [b for b in range(1, NUM_BLOCKS) if b not in named]
    for i, n in enumerate(LENGTHS):     # tables run on past the length
        for b in range(-(-n // BS), WIDTH):
            tables[i, b] = spare.pop()
    clean = _kernel(q, kp, vp, jnp.asarray(tables), lens)
    poison = np.zeros((NUM_BLOCKS, BS), bool)
    poison[1:] = True                   # the trash block stays finite
    for i, n in enumerate(LENGTHS):
        for b in range(-(-n // BS)):
            poison[tables[i, b]] = False
        if n % BS:
            poison[tables[i, n // BS], n % BS:] = True
    mask = jnp.asarray(poison)[:, :, None, None]
    kp2 = jnp.where(mask, jnp.nan, kp)
    vp2 = jnp.where(mask, jnp.nan, vp)
    assert bool(jnp.isnan(kp2).any())
    out = _kernel(q, kp2, vp2, jnp.asarray(tables), lens)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(clean, out)


def _path(kp, tables, hd=HD):
    """'kernel' or 'gather': which path paged_attention traces."""
    q, _, lens = _rows([BS], tables.shape[0], hd=hd)
    text = str(jax.make_jaxpr(lambda *a: pa.paged_attention(*a))(
        q, kp, kp, tables, lens))
    return 'kernel' if 'pallas_call' in text else 'gather'


class TestGate:
    def test_the_serving_shape_takes_the_kernel(self, interpret_mode):
        kp, _ = _pools('float32')
        _, tables, _ = _rows([BS], 8)
        assert pa.can_use_pallas(kp, tables)
        assert _path(kp, tables) == 'kernel'

    def test_a_cpu_without_interpret_mode_takes_the_reference(self):
        assert not _gating.INTERPRET
        kp, _ = _pools('float32')
        _, tables, _ = _rows([BS], 8)
        assert not pa.can_use_pallas(kp, tables)
        assert _path(kp, tables) == 'gather'

    def test_head_dim_64_takes_the_reference(self, interpret_mode):
        kp, _ = _pools('float32', hd=64)
        _, tables, _ = _rows([BS], 8)
        assert not pa.can_use_pallas(kp, tables)
        assert _path(kp, tables, hd=64) == 'gather'

    def test_a_mesh_takes_the_reference(self, interpret_mode):
        from paddle_tpu.distributed import env
        kp, _ = _pools('float32')
        _, tables, _ = _rows([BS], 8)
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                    ('dp', 'tp'))
        env.set_mesh(mesh)
        try:
            assert not pa.can_use_pallas(kp, tables)
            assert _path(kp, tables) == 'gather'
        finally:
            env.set_mesh(None)
        assert pa.can_use_pallas(kp, tables)

    @pytest.mark.parametrize('why, kwargs', [
        ('a block of 4 positions', dict(bs=4)),
        ('12 heads', dict(nh=12)),
        ('8 heads in bfloat16', dict(nh=8, dtype='bfloat16')),
        ('a float16 pool', dict(dtype='float16')),
        ('a block past one fetch round', dict(nh=32, bs=64, hd=256)),
        ('tables past SMEM', dict(batch=512, width=128)),
    ])
    def test_other_shapes_take_the_reference(self, interpret_mode, why,
                                             kwargs):
        kw = dict(nh=NH, bs=BS, hd=HD, dtype='float32', batch=8,
                  width=WIDTH)
        kw.update(kwargs)
        pool = jax.ShapeDtypeStruct(
            (NUM_BLOCKS, kw['bs'], kw['nh'], kw['hd']),
            jnp.dtype(kw['dtype']))
        tables = jax.ShapeDtypeStruct((kw['batch'], kw['width']),
                                      jnp.int32)
        assert not pa.can_use_pallas(pool, tables), why


# -- the kernel at the serve configuration's widths, for a described chip ----
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


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('batch', [8, 32])
def test_mosaic_compiles_the_kernel_at_serving_widths(one_chip, dtype,
                                                      batch):
    """Cerebras-GPT-1.3B's decode: 16 heads of 128, blocks of 16, a
    pool of 832 blocks, tables of 128.  Compiled, not run."""
    nb, nh, bs, hd, width = 832, 16, 16, 128, 128

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_chip)

    pool, tables = sd((nb, bs, nh, hd), dtype), sd((batch, width), 'int32')
    compiled = _uncached(lambda: pa._paged_decode.lower(
        sd((batch, nh, hd), 'float32'), pool, pool, tables,
        sd((batch,), 'int32'),
        chunk=pa._blocks_a_round(pool, tables)).compile())
    text = compiled.as_text()
    assert 'tpu_custom_call' in text and 'paged_decode' in text


@pytest.mark.parametrize('bh,t,d,dtype', [(64, 2048, 128, 'bfloat16'),
                                          (96, 1024, 64, 'bfloat16'),
                                          (64, 2048, 128, 'float32')])
def test_mosaic_compiles_the_flash_kernels_at_training_widths(one_chip, bh,
                                                             t, d, dtype):
    """train_seq2048's attention (4 x 16 heads of 128 at T=2048, the
    blocks its table entry resolves) and chip_smoke.py's GPT-2 small,
    causal, forward and backward; float32 as a model without AMP hands
    it over.  Compiled, not run, under this suite's
    jax_default_matmul_precision 'highest', which Mosaic takes for
    float32 operands and refuses for bfloat16 ones.  (Here with the
    other described-chip compiles: one process of a suite may hold the
    TPU's compiler.)"""
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    bq, bk = fa._tuned_blocks(t, t, d, True)
    x = jax.ShapeDtypeStruct((bh, t, d), jnp.dtype(dtype),
                             sharding=one_chip)
    step = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fa._flash(
            q, k, v, True, d ** -0.5, bq, bk).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    text = _uncached(lambda: step.lower(x, x, x).compile()).as_text()
    for name in ('flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv'):
        assert any('tpu_custom_call' in line and name in line
                   for line in text.splitlines()), name


def test_mosaic_compiles_the_grouped_kernel_at_the_routed_widths(one_chip):
    """smallthinker_21b_serve's decode: 28 query heads on 4 key/value
    heads of 128, blocks of 16 folded to [16, 512], 32 rows, tables of
    800.  Compiled, not run."""
    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_chip)

    pool, tables = sd((8257, 16, 512), 'float32'), sd((32, 800), 'int32')
    assert pa._blocks_a_round_grouped(pool, tables) == 32
    text = _uncached(lambda: pa._paged_decode_grouped.lower(
        sd((32, 28, 128), 'float32'), pool, pool, tables,
        sd((32,), 'int32'), sd((32,), 'int32'),
        chunk=32).compile()).as_text()
    assert 'tpu_custom_call' in text and 'paged_decode_grouped' in text


@pytest.mark.parametrize('t,window', [(2048, 4096), (12288, 4096),
                                      (12288, None)])
def test_mosaic_compiles_the_window_flash_kernel_at_the_routed_widths(
        one_chip, t, window):
    """smallthinker_21b_serve's prefill: 28 query heads on 4 key/value
    heads of 128, bfloat16 as the decoder hands them over, tiles of
    (512, 1024), a window of 4,096 in the window layers and none in
    the full ones."""
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    bq, bk = 512, 1024          # models/routed_window.py::_attend_whole
    q = jax.ShapeDtypeStruct((28, t, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, t, 128), jnp.bfloat16, sharding=one_chip)
    step = jax.jit(lambda q, k, v: fa._flash(
        q, k, v, True, 128 ** -0.5, bq, bk, window))
    text = _uncached(lambda: step.lower(q, kv, kv).compile()).as_text()
    name = 'flash_fwd_window' if window else 'flash_fwd'
    assert any('tpu_custom_call' in line and name in line
               for line in text.splitlines())


@pytest.mark.parametrize('rows', [6144, 24576, 73728])
def test_mosaic_compiles_the_grouped_matmul_at_the_routed_widths(one_chip,
                                                                 rows):
    """smallthinker_21b_serve's prefill: six experts a position of the
    shortest, a middle and the longest bucket, sorted, against 64
    experts of 2560 x 768 (gate and up in one pass) and 768 x 2560
    (down), bfloat16, at the entry points' tile height; a whole expert
    in one block needs more than Mosaic's default 16 MiB of VMEM."""
    import importlib
    gm = importlib.import_module('paddle_tpu.ops.grouped_matmul')

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_chip)

    tm = gm.TILE_ROWS
    wide, sizes = sd((64, 2560, 768), 'bfloat16'), sd((64,), 'int32')
    step = jax.jit(lambda x, wg, wu, wd, sizes: gm._grouped(
        gm._grouped(x, (wg, wu), sizes, tm=tm, dtype=jnp.bfloat16),
        (wd,), sizes, tm=tm, dtype=jnp.float32))
    text = _uncached(lambda: step.lower(
        sd((rows, 2560), 'bfloat16'), wide, wide,
        sd((64, 768, 2560), 'bfloat16'), sizes).compile()).as_text()
    for name in ('grouped_gate_up', 'grouped_matmul'):
        assert any('tpu_custom_call' in line and name in line
                   for line in text.splitlines()), name


def test_mosaic_compiles_the_kernels_at_the_shared_expert_decoders_widths(
        one_chip):
    """trinity_mini_serve (PR 35), compiled, not run.  Its decode: 32
    query heads on 4 key/value heads of 128, 64 rows, tables of 384,
    the window group's pool of 8,321 blocks.  Its prefill: the longest
    bucket, 2,048 positions, through the window flash kernel at a
    window of 2,048 and tiles of (512, 1024), and the grouped expert
    product of 8 experts a position against 128 experts of 2048 x
    1024 with SiLU in the epilogue and 1024 x 2048 back (20 and 13 MB
    of the gate's 48 MB of VMEM); the shortest bucket, 256 positions,
    gives an expert 16 rows."""
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    gm = importlib.import_module('paddle_tpu.ops.grouped_matmul')

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_chip)

    pool, tables = sd((8321, 16, 512), 'float32'), sd((64, 384), 'int32')
    chunk = pa._blocks_a_round_grouped(pool, tables)
    text = _uncached(lambda: pa._paged_decode_grouped.lower(
        sd((64, 32, 128), 'float32'), pool, pool, tables,
        sd((64,), 'int32'), sd((64,), 'int32'),
        chunk=chunk).compile()).as_text()
    assert 'tpu_custom_call' in text and 'paged_decode_grouped' in text

    q, kv = sd((32, 2048, 128), 'bfloat16'), sd((4, 2048, 128), 'bfloat16')
    step = jax.jit(lambda q, k, v: fa._flash(
        q, k, v, True, 128 ** -0.5, 512, 1024, 2048))
    text = _uncached(lambda: step.lower(q, kv, kv).compile()).as_text()
    assert any('tpu_custom_call' in line and 'flash_fwd_window' in line
               for line in text.splitlines())

    tm = gm.TILE_ROWS
    wide, sizes = sd((128, 2048, 1024), 'bfloat16'), sd((128,), 'int32')
    assert gm._vmem_bytes(tm, 2048, 1024, 2, 2) < gm.VMEM_BUDGET / 2
    step = jax.jit(lambda x, wg, wu, wd, sizes: gm._grouped(
        gm._grouped(x, (wg, wu), sizes, tm=tm, dtype=jnp.bfloat16,
                    activation='silu'),
        (wd,), sizes, tm=tm, dtype=jnp.float32))
    for rows in (256 * 8, 2048 * 8):
        text = _uncached(lambda: step.lower(
            sd((rows, 2048), 'bfloat16'), wide, wide,
            sd((128, 1024, 2048), 'bfloat16'), sizes).compile()).as_text()
        for name in ('grouped_gate_up', 'grouped_matmul'):
            assert any('tpu_custom_call' in line and name in line
                       for line in text.splitlines()), (rows, name)


# -- the GPT serving modules for the same described chip ---------------------
def _results_of(text, shape, ops):
    """The instructions of an HLO text whose operation is one of `ops`
    (or whose name says so: a fusion is named after what it fuses) and
    whose result holds an array of `shape`."""
    import re
    line_re = re.compile(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(')
    found = []
    for line in text.splitlines():
        m = line_re.match(line)
        if m and shape in m.group(2) and any(
                op == m.group(3) or op in m.group(1) for op in ops):
            found.append(line.strip()[:160])
    return found


@pytest.fixture(scope='module')
def gpt_serving_engine():
    """Cerebras-GPT-1.3B's serving widths at 2 layers (and a small
    vocabulary: the head is no part of what is asked here) under the
    benchmark's ServeConfig, pool of 832 blocks and all."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServeConfig, ServingEngine
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=1024, hidden_size=2048, num_layers=2, num_heads=16,
        intermediate_size=8192, max_seq_len=2048, dropout=0.0))
    model.eval()
    model.to('bfloat16')
    return ServingEngine(model, ServeConfig(
        block_size=16, max_model_len=2048, max_slots=32, decode_span=8,
        prompt_buckets=(128, 256, 512, 1024), prefill_batch=1,
        batch_buckets=(8, 16, 32), temperature=0.0, num_blocks=832))


@pytest.mark.parametrize('module', ['decode[32x8]', 'decode[8x8]',
                                    'prefill[1024x1]'])
def test_the_serving_modules_hold_the_kv_pool_once(
        one_chip, monkeypatch, gpt_serving_engine, module):
    """The engine's own module bodies, compiled for the described chip
    with the pools donated: every pool is aliased from argument to
    result, nothing of a pool's size is copied, sliced or joined round
    the scan (PR 24's finding 2: with heads outside positions XLA kept
    the carry in another layout and held every pool twice), and the
    temporaries stay under one pool.  Both orders read
    f32[832,16,16,128] at these widths, so the shape cannot tell."""
    eng = gpt_serving_engine
    # the chip's path: a TPU is what the module is compiled for
    monkeypatch.setattr(_gating, 'pallas_backend_ok', lambda: True)
    if module.startswith('decode'):
        batch = int(module[7:].split('x')[0])
        fn, _, example, name, donate = eng._decode_spec(batch, 8)
        assert eng.cache.decode_path(eng.model, batch, 128) == 'kernel'
    else:
        fn, _, example, name, donate = eng._prefill_spec(1024, 1)
    assert name == f'serve.{module}'
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), example)
    compiled = _uncached(lambda: jax.jit(
        fn, donate_argnums=donate).lower(*avals).compile())
    text = compiled.as_text()
    if module.startswith('decode'):
        assert 'paged_decode' in text and 'tpu_custom_call' in text
    pool = eng.cache.pools[0][0]
    assert pool.shape == (832, 16, 16, 128) and pool.dtype == jnp.float32
    one_pool = pool.size * 4
    pools = 2 * eng.cache.num_layers * one_pool
    moved = _results_of(text, 'f32[832,16,16,128]',
                        ('copy', 'copy-start', 'slice-start',
                         'ConcatBitcast'))
    assert not moved, moved[:4]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pools, memory
    assert memory.temp_size_in_bytes < one_pool, memory


@pytest.mark.parametrize('kind', ['decode', 'prefill'])
def test_a_modules_fingerprint_carries_the_pools_order(kind):
    """An artifact the exec tier kept for a pool with heads outside
    positions has the same avals at 16 heads of 16 positions;
    `_fingerprint` hashes no avals, so the marker is what keeps it
    from being loaded against this pool."""
    eng = _engine()
    assert eng.cache.layout_key == 'block,position,head,dim'
    if kind == 'decode':
        fp = eng._decode_spec(4, 2)[1]
        before = eng._fingerprint(
            'serve-decode', batch=4, span=2, keys='per-request-pos',
            paged=eng.cache.decode_path(eng.model, 4, 8))
    else:
        fp = eng._prefill_spec(16, 1)[1]
        before = eng._fingerprint(
            'serve-prefill', bucket=16, nblk=2, chunk=1,
            keys='per-request-pos')
    assert fp is not None and before is not None and fp != before


# -- the engine on the kernel path, and the counter of what it reads ---------
def _engine(**config):
    from paddle_tpu.models.gpt import gpt_tiny
    from paddle_tpu.serving import ServeConfig, ServingEngine
    paddle.seed(7)
    model = gpt_tiny(num_layers=2, hidden_size=8 * HD, num_heads=8,
                     max_seq_len=64)
    model.eval()
    kw = dict(block_size=8, max_slots=4, decode_span=2,
              prompt_buckets=(8, 16), batch_buckets=(1, 2, 4),
              prefill_batch=1, max_model_len=64, temperature=0.0)
    kw.update(config)
    return ServingEngine(model, ServeConfig(**kw))


def _serve(prompts, new_tokens):
    eng = _engine()
    for i, (p, n) in enumerate(zip(prompts, new_tokens)):
        eng.submit(np.asarray(p), n, rid=f'r{i}')
    report = eng.run()
    assert report['audit'] == []
    return {r.rid: list(r.tokens) for r in eng.scheduler.finished}, report


def test_the_engine_decodes_the_same_tokens_on_either_path(monkeypatch):
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, 200, size=n) for n in (5, 11, 3, 16, 9)]
    new_tokens = [7, 12, 9, 5, 17]
    gather, report = _serve(prompts, new_tokens)
    assert report['paged_kernel'] is False
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    kernel, report = _serve(prompts, new_tokens)
    assert report['paged_kernel'] is True
    assert kernel == gather
    assert [len(kernel[f'r{i}']) for i in range(5)] == new_tokens
    for name in ('kv_blocks_read', 'kv_blocks_table', 'kv_read_share'):
        assert name in report
    assert 0 < report['kv_read_share'] < 1
    assert report['kv_read_share'] == \
        report['kv_blocks_read'] / report['kv_blocks_table']


def test_the_engine_counts_the_blocks_a_known_plan_reads(interpret_mode):
    """One request of 5 positions and 7 tokens, blocks of 8, spans of
    2, tables of 8 blocks.  Its three dispatches start at contexts 5,
    7 and 9 and end at 7, 9 and 11: 1, 2 and 2 blocks a token step,
    two token steps each, of tables of 8."""
    from paddle_tpu import telemetry
    telemetry.reset()
    eng = _engine()
    eng.submit(np.arange(1, 6), 7)
    report = eng.run()
    assert report['interventions'] == 3
    assert report['kv_blocks_read'] == 2 * (1 + 2 + 2)
    assert report['kv_blocks_table'] == 2 * 3 * 8
    assert report['kv_read_share'] == 10 / 48
    assert report['paged_kernel'] is True
    steps = [e for e in telemetry.events('serve_step') if e['span']]
    assert [e['kv_blocks_read'] for e in steps] == [1, 2, 2]
    assert [e['kv_blocks_table'] for e in steps] == [8, 8, 8]
    stats = eng.stats()
    assert (stats['kv_blocks_read'], stats['kv_blocks_table'],
            stats['paged_kernel']) == (10, 48, True)


def test_a_plan_counts_one_block_for_a_row_that_is_not_active():
    from paddle_tpu.serving.scheduler import DecodePlan
    plan = DecodePlan([], 4, 8, span=2)
    plan.ctx[:2] = [5, 17]
    plan.active[:2] = True
    # ends at 7 and 19: 1 and 3 blocks of 8; the two padding rows one
    assert plan.kv_blocks(8) == (1 + 3 + 1 + 1, 4 * 8)
    plan.ctx[1] = 70            # past the table: no more than it holds
    assert plan.kv_blocks(8) == (1 + 8 + 1 + 1, 4 * 8)


# -- the retention decoder's modules for the same described chip --------------
# (kept in this file: one worker describes the topology, PERF.md PR 26)
def _retention_param_shapes(cfg):
    h, d, inter = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    hq, hkv = cfg.num_heads * d, cfg.num_kv_heads * d
    shapes = {'model.embed.weight': (cfg.vocab_size, h),
              'lm_head.weight': (cfg.vocab_size, h),
              'model.norm.weight': (h,)}
    for i in range(cfg.num_layers):
        pre = f'model.layers.{i}.'
        shapes.update({
            pre + 'input_norm.weight': (h,), pre + 'post_norm.weight': (h,),
            pre + 'attn.q_proj.weight': (h, hq),
            pre + 'attn.k_proj.weight': (h, hkv),
            pre + 'attn.v_proj.weight': (h, hkv),
            pre + 'attn.g_proj.weight': (h, cfg.num_kv_heads),
            pre + 'attn.o_proj.weight': (hq, h),
            pre + 'attn.q_norm.weight': (d,),
            pre + 'attn.k_norm.weight': (d,),
            pre + 'mlp.gate_proj.weight': (h, inter),
            pre + 'mlp.up_proj.weight': (h, inter),
            pre + 'mlp.down_proj.weight': (inter, h)})
    return shapes


def test_the_shapes_below_are_the_retention_models_own():
    from paddle_tpu.models.retention import retention_tiny
    paddle.seed(0)
    model = retention_tiny()
    params, _ = model.functional_state()
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == _retention_param_shapes(model.config)


def test_mosaic_compiles_the_retention_update_at_serving_widths(one_chip):
    """Brumby-14B's decode: 16 rows, 8 key/value heads of 128, groups
    of 5 query heads padded to 8, 8,320 features.  Compiled, not run;
    the state goes in and comes out in place (a caller that does not
    donate it, as here, gets XLA's copy in front)."""
    from paddle_tpu.ops import power_retention as pr
    R, hkv, g8, d, D = 16, 8, 8, 128, 8320

    def sd(shape, dt='float32'):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    compiled = _uncached(lambda: pr._retention_decode.lower(
        sd((R,), 'int32'), sd((R, hkv, g8, D)), sd((R, hkv, 1, D)),
        sd((R, hkv, d, 1)), sd((R, hkv, 1, 1)), sd((R, hkv, d, D)),
        tile=pr._tile(d)).compile())
    text = compiled.as_text()
    assert 'tpu_custom_call' in text and 'retention_decode' in text
    # the kernel's second result is its sixth operand, rewritten
    assert 'output_to_operand_aliasing={{1}: (5, {})}' in text


def test_the_decode_module_holds_the_recurrent_state_once(one_chip,
                                                          monkeypatch):
    """serve.decode[16x8] of the retention decoder at Brumby-14B's
    widths and 2 layers, through the engine's own builder, for the
    described chip: the states are donated in and aliased out, nothing
    of a state's size is copied inside the scan (PR 24's finding 2 was
    a pool held twice), and the temporaries stay far under one layer's
    state."""
    from paddle_tpu.models.retention import (RetentionConfig,
                                             RetentionForCausalLM)
    from paddle_tpu.ops import power_retention as pr
    from paddle_tpu.serving import ServeConfig, ServingEngine
    from paddle_tpu.serving.kv_cache import RecurrentStateCache
    slots, span = 16, 8
    cfg = RetentionConfig(num_layers=2)

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    params = {k: sd(v, 'bfloat16')
              for k, v in _retention_param_shapes(cfg).items()}
    # the engine's builder on a model and a cache that hold no arrays
    model = RetentionForCausalLM.__new__(RetentionForCausalLM)
    model.config = cfg
    eng = ServingEngine.__new__(ServingEngine)
    eng.model, eng.recurrent = model, True
    eng.config = ServeConfig(
        max_slots=slots, decode_span=span, prompt_buckets=(256,),
        batch_buckets=(slots,), prefill_batch=1, max_model_len=2048,
        temperature=0.0).resolved(cfg)
    eng.cache = RecurrentStateCache(
        **model.state_spec(), slots=slots, max_model_len=2048,
        device_init=False)
    # the chip's path: a TPU is what the module is compiled for
    monkeypatch.setattr(_gating, 'pallas_backend_ok', lambda: True)
    S = sd((slots, 8, 128, 8320), 'float32')
    z = sd((slots, 8, 8320), 'float32')
    assert pr.can_use_pallas(S, sd((slots, 40, 128), 'float32'))
    row = sd((slots,), 'int32')
    fn = eng._decode_build(slots, span)
    compiled = _uncached(lambda: jax.jit(fn, donate_argnums=(2, 3, 4)).lower(
        params, {}, (S, S), (z, z), sd((slots + 1,), 'int32'), row, row,
        row, sd((slots,), 'bool'), row, row).compile())
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 1
    assert 'retention_decode' in text
    state = 2 * slots * 8 * 8320 * 129 * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state
    one_layer = state // 2
    assert memory.temp_size_in_bytes < one_layer // 2, memory
    copies = _results_of(text, 'f32[16,8,128,8320]',
                         ('copy', 'copy-start'))
    assert not copies, copies[:3]
