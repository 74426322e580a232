"""The routed-expert decoder with window and full layers (PR 33) at a
small size on the CPU: `forward` and prefill-then-decode through
`ServingEngine` against the plain float32 reference by logits, the
routed layer against a per-token loop (its grouped product through
`ragged_dot` and, in interpret mode at widths its gate takes, through
the Pallas kernel of PR 34), a prefill's pad positions, the two-group
allocator under churn, and which MoE the engine serves."""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import routed_window as rw
from paddle_tpu.ops import _gating
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.serving.kv_cache import LayerGroupKVCache
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          Request)
from benchmark import logit_gap
from benchmark.reference import smallthinker_ref as ref

SERVE = dict(block_size=4, max_slots=4, decode_span=2,
             prompt_buckets=(8, 16, 32), batch_buckets=(4,),
             prefill_batch=1, max_model_len=64, num_blocks=40)


@pytest.fixture(scope='module')
def tiny():
    paddle.seed(0)
    model = rw.routed_window_tiny()
    params, _ = model.functional_state()
    return model, params, dict(vars(model.config))


def test_forward_matches_the_reference(tiny):
    model, params, cfg = tiny
    ids = np.random.default_rng(0).integers(0, 128, (2, 40))
    got = np.asarray(model.forward(jnp.asarray(ids)).value)
    want = np.asarray(ref.logits_at(
        params, ids, np.tile(np.arange(40), (2, 1)), model=cfg))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_engine_prefill_then_decode_matches_the_reference(tiny):
    """Unequal rows, prompts over the window of 8, decoding across
    releases: every served token is the reference's best at its
    position to within rounding (logits are compared, never tokens)."""
    model, params, cfg = tiny
    eng = ServingEngine(model, ServeConfig(**SERVE))
    assert isinstance(eng.cache, LayerGroupKVCache)
    eng.warmup()
    compiled = eng.compile_count
    rng = np.random.default_rng(0)
    reqs = [Request(f'r{i}', rng.integers(0, 128, n), new, arrival_t=0.0)
            for i, (n, new) in enumerate(
                [(5, 20), (13, 9), (30, 25), (27, 30), (8, 12), (16, 16)])]
    report = eng.run(reqs)
    assert eng.compile_count == compiled
    assert report['audit'] == []
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    assert all(r.state == Request.DONE for r in reqs)
    counts = eng.counts()
    assert counts['window_blocks_released'] > 0
    assert counts['held_block_layers'] < counts['one_table_block_layers']
    decoded = sum(len(r.tokens) - 1 for r in reqs)
    assert counts['moe_assignments'] == decoded * 3 * 4
    assert counts['prefill_tokens'] == sum(r.prompt.size for r in reqs)
    gaps, _same, margin, _ = logit_gap.gaps(
        functools.partial(ref.logits_at, params, model=cfg),
        [(r.prompt, list(r.tokens)) for r in reqs], 64, 30, block=1)
    assert gaps.max() <= 1e-5
    assert margin.min() > 0


def test_the_decode_modules_taps_match_the_reference(tiny):
    """What the engine's own decode module hands out (`step_taps`): the
    first full and the first window layer's router logits, attention
    output and routed output a row and token step, for a request that
    decodes across releases, against the reference at those
    positions."""
    model, params, cfg = tiny
    eng = ServingEngine(model, ServeConfig(**SERVE))
    assert eng.cache.tap_layers == (0, 1)
    req = Request('tap', np.random.default_rng(3).integers(0, 128, 27), 20,
                  arrival_t=0.0)
    eng.submit(req)
    while len(req.tokens) < 13:
        eng.step()
    span = eng.config.decode_span
    assert eng.cache.owned_window(req.rid)[0] > 0   # blocks were released
    ids = np.concatenate([req.prompt, req.tokens])[:req.ctx]
    want = ref.taps_at_end(params, ids, (0, 1), span, model=cfg)
    row = eng.scheduler.running.index(req)
    for name, got in eng.step_taps.items():         # [span, 2, rows, ...]
        for j, layer in enumerate((0, 1)):
            np.testing.assert_allclose(
                np.asarray(got)[:, j, row], np.asarray(want[layer][name]),
                rtol=1e-4, atol=1e-5)
    eng.run()
    assert eng.scheduler.audit() == []


# -- the routed layer ---------------------------------------------------------------
def _loop(p, h, logits, k):
    out = np.zeros(h.shape, np.float64)
    p = {n: np.asarray(v, np.float64) for n, v in p.items()}
    for t in range(h.shape[0]):
        top = np.argsort(-logits[t], kind='stable')[:k]
        w = np.exp(logits[t, top] - logits[t, top].max())
        w /= w.sum()
        for e, we in zip(top, w):
            a = np.maximum(h[t] @ p['gate_proj'][e], 0) \
                * (h[t] @ p['up_proj'][e])
            out[t] += we * (a @ p['down_proj'][e])
    return out


def _layer(hidden, width, rows, dtype):
    rs = np.random.RandomState(0)
    p = {'gate_proj': rs.randn(8, hidden, width) * .1,
         'up_proj': rs.randn(8, hidden, width) * .1,
         'down_proj': rs.randn(8, width, hidden) * .1}
    return ({n: jnp.asarray(v, dtype) for n, v in p.items()},
            rs.randn(rows, hidden).astype('f4'),
            rs.randn(rows, 8).astype('f4'))


# how the grouped product is run: through `ragged_dot` (the tests'
# widths, float32), through the Pallas kernel in interpret mode (a layer
# whose widths its gate takes: 128 rows x 3 = three tiles of 128,
# bfloat16), or the decode step's dense product
PROGRAMS = {True: (64, 32, 24, jnp.float32, 1e-5),
            'kernel': (128, 128, 128, jnp.bfloat16, 2e-2),
            False: (64, 32, 24, jnp.float32, 1e-5)}


@pytest.fixture(params=list(PROGRAMS), ids=lambda g: f'grouped={g}')
def layer(request, monkeypatch):
    """(parameters, rows, router logits), `grouped`, the lowered text's
    word for the program, and the tolerance against float64."""
    grouped = request.param
    hidden, width, rows, dtype, tol = PROGRAMS[grouped]
    monkeypatch.setattr(_gating, 'INTERPRET', grouped == 'kernel')
    return (_layer(hidden, width, rows, dtype), bool(grouped),
            {True: 'ragged_dot', 'kernel': 'grouped_gate_up',
             False: 'dot_general'}[grouped], tol)


def test_routed_layer_matches_a_per_token_loop(layer):
    (p, h, logits), grouped, word, tol = layer
    T = h.shape[0]
    with jax.default_matmul_precision('highest'):
        fn = jax.jit(functools.partial(rw.routed_experts, k=3,
                                       grouped=grouped))
        out, stats = fn(p, jnp.asarray(h), jnp.asarray(logits))
    # the gate sent the shapes where the fixture says (the traced
    # program's own words: the CPU lowers a ragged dot to plain ones)
    text = str(jax.make_jaxpr(fn)(p, jnp.asarray(h), jnp.asarray(logits)))
    assert word in text
    assert ('ragged_dot' in text) == (word == 'ragged_dot')
    assert ('pallas_call' in text) == (word == 'grouped_gate_up')
    rounded = np.asarray(jnp.asarray(h).astype(p['gate_proj'].dtype),
                         np.float64)
    want = _loop(p, rounded, logits, 3)
    assert np.abs(np.asarray(out) - want).max() <= tol * np.abs(want).max()
    load = np.bincount(np.argsort(-logits, 1, kind='stable')[:, :3].ravel(),
                       minlength=8)
    assert list(np.asarray(stats)) == [3 * T, (load > 0).sum(), load.max()]


def test_a_pad_row_moves_no_other_row_bitwise(layer):
    (p, h, logits), grouped, _, _ = layer
    T = h.shape[0]
    fn = jax.jit(functools.partial(rw.routed_experts, k=3, grouped=grouped))
    base, _ = fn(p, jnp.asarray(h), jnp.asarray(logits))
    h2, l2 = h.copy(), logits.copy()
    h2[5], l2[5] = 100.0, -logits[5]          # another row, other experts
    moved, _ = fn(p, jnp.asarray(h2), jnp.asarray(l2))
    keep = np.arange(T) != 5
    assert np.array_equal(np.asarray(base)[keep], np.asarray(moved)[keep])
    assert not np.array_equal(np.asarray(base)[5], np.asarray(moved)[5])


@pytest.mark.parametrize('layer', [True, 'kernel'], indirect=True,
                         ids=lambda g: f'grouped={g}')
def test_rows_that_are_not_active_are_zero_and_move_no_other_row(layer):
    """A prefill's pad positions: routed nowhere, multiplied by
    nothing, zero in the result; the true rows' results are the ones
    they had with the pad rows computed, bit for bit."""
    (p, h, logits), _, _, _ = layer
    T = h.shape[0]
    fn = jax.jit(functools.partial(rw.routed_experts, k=3, grouped=True))
    base, _ = fn(p, jnp.asarray(h), jnp.asarray(logits))
    active = np.arange(T) % 24 < 17                  # ragged true lengths
    got, stats = fn(p, jnp.asarray(h), jnp.asarray(logits),
                    active=jnp.asarray(active))
    assert np.array_equal(np.asarray(got)[active], np.asarray(base)[active])
    assert not np.asarray(got)[~active].any()
    assert int(stats[0]) == 3 * active.sum()


def test_counts_follow_the_active_rows():
    p, h, logits = _layer(64, 32, 24, jnp.float32)
    active = jnp.asarray(np.arange(24) < 4)
    _, stats = rw.routed_experts(p, jnp.asarray(h), jnp.asarray(logits), 3,
                                 grouped=False, active=active)
    assert int(stats[0]) == 12 and int(stats[1]) <= 8


# -- a prefill's pad positions, and which program its modules took ----------------------
def _wide(monkeypatch):
    """A model whose routed layers pass the kernel's gate in interpret
    mode: widths of 128, bfloat16, a bucket of 128 x 3 experts = three
    tiles of rows."""
    monkeypatch.setattr(_gating, 'INTERPRET', True)
    paddle.seed(1)
    return rw.routed_window_tiny(hidden_size=128, intermediate_size=128,
                                 num_layers=2, window_layout=(0, 1),
                                 rope_layout=(0, 1), max_seq_len=256,
                                 dtype='bfloat16')


@pytest.mark.parametrize('program', ['ragged_dot', 'kernel'])
def test_a_prefill_short_of_its_bucket_returns_what_it_did_with_the_pad_rows(
        tiny, monkeypatch, program):
    """Logits at the last true position and every true position's keys
    and values are the numbers the prefill returned when the routed
    layers computed the pad positions too."""
    from paddle_tpu.serving.kv_cache import PrefillKV
    model = tiny[0] if program == 'ragged_dot' else _wide(monkeypatch)
    assert model.prefill_path(2, 128) == program
    params, _ = model.functional_state()
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 128, (2, 128)))
    lengths = jnp.asarray([77, 128], jnp.int32)

    def run():
        caches = [PrefillKV(lengths=lengths)
                  for _ in range(model.config.num_layers)]
        logits, views = jax.jit(model.prefill)(params, None, ids, 0, caches)
        return (np.asarray(logits),
                [(np.asarray(v.k), np.asarray(v.v)) for v in views])

    logits, kv = run()
    every_row = rw.routed_experts
    monkeypatch.setattr(
        rw, 'routed_experts', lambda *a, active=None, **kw: every_row(
            *a, active=None, **kw))
    want_logits, want_kv = run()
    assert np.array_equal(logits, want_logits)
    true = np.arange(128)[None, :] < np.asarray(lengths)[:, None]
    moved = False
    for (k, v), (wk, wv) in zip(kv, want_kv):
        assert np.array_equal(k[true], wk[true])
        assert np.array_equal(v[true], wv[true])
        moved |= not np.array_equal(k[~true], wk[~true])
    assert moved          # the pad positions' own rows did change


@pytest.mark.parametrize('program', ['ragged_dot', 'kernel'])
def test_the_engine_counts_the_prefills_that_took_the_kernel(
        tiny, monkeypatch, program):
    model = tiny[0] if program == 'ragged_dot' else _wide(monkeypatch)
    eng = ServingEngine(model, ServeConfig(**{
        **SERVE, 'prompt_buckets': (128,), 'max_model_len': 160,
        'num_blocks': 90}))
    assert eng.counts()['moe_kernel_prefills'] == 0
    rng = np.random.default_rng(0)
    reqs = [Request(f'r{i}', rng.integers(0, 128, n), 4, arrival_t=0.0)
            for i, n in enumerate([100, 128, 9])]
    report = eng.run(reqs)
    assert report['audit'] == [] and eng._prefills == 3
    assert report['moe_kernel_prefills'] == eng.counts()[
        'moe_kernel_prefills'] == (3 if program == 'kernel' else 0)


# -- the two-group allocator ----------------------------------------------------------
def _cache(**kw):
    args = dict(block_size=4, num_blocks=64, max_slots=4, decode_span=2,
                device_init=False)
    args.update(kw)
    return LayerGroupKVCache((None, 8, 8, 8), 2, 16, **args)


def test_window_group_releases_and_stays_inside_its_bound():
    cache = _cache()
    assert cache.window_bound == 4          # blocks_for(8 + 2, 4) + 1
    assert cache.groups[1].num_blocks == 4 * 4 + 1
    # a prompt of 30 > window 8: the blocks below 30 + 1 - 8 are never held
    assert cache.ensure('a', 32, written=30)
    first, blocks = cache.owned_window('a')
    assert first == 23 // 4 and len(blocks) == 8 - first
    assert len(cache.owned('a')) == 8
    row = cache.table_row('a', 16)
    assert (row[1, :first] == 0).all() and (row[1, first:8] > 0).all()
    for written in range(30, 60):
        assert cache.ensure('a', written + 2, written=written)
        first, blocks = cache.owned_window('a')
        assert len(blocks) <= cache.window_bound
        assert first * 4 <= max(0, written + 1 - 8)     # still visible
        assert (first + 1) * 4 > max(0, written + 1 - 8)
        assert cache.audit() == []
    assert cache.counters['window_blocks_released'] > 0
    held = len(cache.owned('a')) + len(blocks)
    assert held == 16 + len(blocks) and cache.free_seq('a') == held
    assert cache.free_blocks == cache.num_blocks - 1


def test_two_group_allocator_under_churn():
    rng = np.random.default_rng(0)
    cache = _cache(num_blocks=24)
    live = {}
    for step in range(400):
        sid = f's{rng.integers(0, 6)}'
        if sid in live and rng.random() < 0.3:
            cache.free_seq(sid)
            del live[sid]
        elif sid in live:
            grown = live[sid] + int(rng.integers(1, 3))
            if cache.ensure(sid, grown + 2, written=grown):
                live[sid] = grown
        elif len(live) < 4:
            n = int(rng.integers(1, 40))
            # a refused admission leaves nothing behind (the audit and
            # the owners below say so): the scheduler tries it again
            if cache.ensure(sid, n + 2, written=n):
                live[sid] = n
            else:
                assert sid not in cache._first
        assert cache.audit() == [], step
        assert set(cache.owners()) == set(live)
    for sid in list(live):
        cache.free_seq(sid)
    assert cache.free_blocks == cache.num_blocks - 1 and not cache.owners()


def test_preemption_frees_both_groups():
    # the full pool holds 12 blocks of 4: three rows of 14 + span cannot
    # all grow, the youngest goes back to the queue with nothing held
    cache = _cache(num_blocks=13)
    sched = ContinuousBatchingScheduler(
        cache, max_slots=4, batch_buckets=(4,), bucket_fn=lambda n: 16,
        max_model_len=64, decode_span=2)
    reqs = [Request(f'r{i}', np.ones(14, np.int64), 30) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    admitted = []
    while (req := sched.admit_next()) is not None:
        req.tokens.append(1)
        admitted.append(req)
    assert len(admitted) == 3
    preempted = []
    for _ in range(8):
        preempted += sched.reserve_span(2)
        for r in sched.running:
            r.ctx += 2
        assert sched.audit() == []
        if preempted:
            break
    assert preempted and preempted[0] is reqs[2]
    assert cache.owned('r2') == [] and cache.owned_window('r2') == (0, [])
    plan = sched.plan()
    assert plan.tables.shape == (4, 2, 16)
    assert cache.kv_blocks(plan)[1] == 2 * 4 * 16


def test_a_request_that_never_fits_is_rejected():
    cache = _cache(num_blocks=5)
    sched = ContinuousBatchingScheduler(
        cache, max_slots=4, batch_buckets=(4,), bucket_fn=lambda n: 32,
        max_model_len=64, decode_span=2)
    with pytest.raises(ValueError, match='KV blocks'):
        sched.submit(Request('big', np.ones(20, np.int64), 10))
    # the bucket's padding is dropped on the trash block: 12 fit in 4
    sched.submit(Request('small', np.ones(9, np.int64), 4))


# -- which routed models the engine serves --------------------------------------------
def test_engine_serves_a_dropless_model_and_refuses_switch_moe(tiny):
    from paddle_tpu.models import gpt_moe_tiny
    model = tiny[0]
    assert model.config.num_experts == 8
    ServingEngine(model, ServeConfig(**SERVE))
    with pytest.raises(ValueError, match='capacity-dropping MoE'):
        ServingEngine(gpt_moe_tiny(), ServeConfig(max_slots=2))


# -- a decode dispatch in flight (PR 36) ------------------------------------------------
def test_a_span_in_flight_changes_no_token_and_the_books_balance(tiny):
    """The span of N+1 is sent while N is on the device, over a pool so
    small that rows are preempted with their span in flight and window
    blocks are released under a span that still reads them: every
    request's tokens are what a one-slot engine gives (one row, each
    span planned with nothing else live), nothing is counted twice and
    both groups come back whole."""
    model, _params, _cfg = tiny
    shapes = [(5, 20), (13, 9), (30, 25), (27, 30), (8, 12), (16, 16)]

    def load():
        rng = np.random.default_rng(0)
        return [Request(f'r{i}', rng.integers(0, 128, n), new,
                        arrival_t=0.0) for i, (n, new) in enumerate(shapes)]

    alone = ServingEngine(model, ServeConfig(**dict(
        SERVE, max_slots=1, batch_buckets=(1,))))
    want = load()
    alone.run(want)
    eng = ServingEngine(model, ServeConfig(**dict(SERVE, num_blocks=34)))
    reqs = load()
    report = eng.run(reqs)
    assert report['counters']['preempted'] >= 1
    assert eng.counts()['window_blocks_released'] > 0
    for got, ref_req in zip(reqs, want):
        assert got.state == Request.DONE
        assert got.tokens == ref_req.tokens, got.rid
    assert report['audit'] == [] and eng._in_flight is None
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    assert eng.decoded_tokens == sum(len(r.tokens) for r in reqs)
    assert report['decoded_tokens'] == sum(new for _, new in shapes)
    # one unbroken run: every dispatch but the first was sent ahead
    assert eng.counts()['decode_dispatches_ahead'] == eng.interventions - 1
