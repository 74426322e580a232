"""The routed decoder with a shared expert, sigmoid scores, gated
attention and a leading dense layer (PR 35) at a small size on the CPU:
`forward`, and prefill then decode through `ServingEngine`'s paged
pools past the window, against the plain float32 reference by logits;
what the decode module hands out against the reference's layers; the
routed layer's two programs against each other and a per-token loop;
the fifteen planted faults, each of which has to show; and that the
routed decoder without these (PR 33's) computes what it computed
before its routed layer was split for this one, bit for bit."""
import functools
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import afmoe as af
from paddle_tpu.models import routed_window as rw
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.serving.kv_cache import LayerGroupKVCache
from paddle_tpu.serving.scheduler import Request
from benchmark import logit_gap
from benchmark.reference import trinity_ref as ref

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                'benchmark_suite'))
import afmoe_faults  # noqa: E402

SERVE = dict(block_size=4, max_slots=4, decode_span=2,
             prompt_buckets=(8, 16, 32), batch_buckets=(4,),
             prefill_batch=1, max_model_len=64, num_blocks=40)


@pytest.fixture(scope='module')
def tiny():
    """The tiny model with norms off 1 and biases off 0, as the
    benchmark draws them: a tensor that is all 1 or all 0 hides the
    fault that drops it."""
    paddle.seed(0)
    model = af.afmoe_tiny()
    rs = np.random.RandomState(1)
    model.set_state_dict({
        k: paddle.to_tensor(np.asarray(v) + 0.1 * rs.randn(*v.shape)
                            .astype('f4'))
        for k, v in model.functional_state()[0].items()
        if 'norm' in k or k.endswith('router.bias')})
    params, _ = model.functional_state()
    return model, params, dict(vars(model.config))


def test_forward_matches_the_reference(tiny):
    model, params, cfg = tiny
    ids = np.random.default_rng(0).integers(0, 128, (2, 40))
    got = np.asarray(model.forward(jnp.asarray(ids)).value)
    want = np.asarray(ref.logits_at(
        params, ids, np.tile(np.arange(40), (2, 1)), model=cfg))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_layers_of_two_layouts_in_one_list(tiny):
    model, params, _ = tiny
    layers = model.model.layers
    assert hasattr(layers[0], 'mlp') and not hasattr(layers[0], 'router')
    assert all(hasattr(layer, 'router') and hasattr(layer, 'shared')
               and not hasattr(layer, 'mlp') for layer in layers[1:])
    assert params['model.layers.1.router.bias'].dtype == jnp.float32
    assert model.cache_spec()['tap_layers'] == (2, 1)
    assert model.cache_spec()['layer_windows'] == (8, 8, None, 8, 8)


def test_the_cache_taps_the_layers_the_model_names():
    """By default the first full and the first window layer; a model
    whose first layer of a kind has no router names its own; a pair
    that is not a full and a window layer is refused."""
    def cache(windows, **kw):
        return LayerGroupKVCache(windows, 2, 16, block_size=4, num_blocks=8,
                                 max_slots=2, decode_span=2,
                                 device_init=False, **kw)

    assert cache((None, 8, 8, 8)).tap_layers == (0, 1)
    assert cache((8, 8, None, 8, 8)).tap_layers == (2, 0)
    assert cache((8, 8, None, 8, 8), tap_layers=(2, 1)).tap_layers == (2, 1)
    with pytest.raises(ValueError, match='tap layers'):
        cache((8, 8, None, 8, 8), tap_layers=(1, 2))


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
    # the counts are of the four ROUTED layers: the dense one has none
    decoded = sum(len(r.tokens) - 1 for r in reqs)
    assert counts['moe_assignments'] == decoded * 3 * 4
    gaps, _same, margin, _ = logit_gap.gaps(
        functools.partial(ref.logits_at, params, model=cfg),
        [(r.prompt, list(r.tokens)) for r in reqs], 64, 30, block=1)
    assert gaps.max() <= 1e-5
    assert margin.min() > 0


def _taps_error(model, params, cfg):
    """The largest relative distance between what the engine's decode
    module handed out for a request that decodes across releases and
    the reference's layers at those positions."""
    eng = ServingEngine(model, ServeConfig(**SERVE))
    layers = eng.cache.tap_layers
    req = Request('tap', np.random.default_rng(3).integers(0, 128, 27), 20,
                  arrival_t=0.0)
    eng.submit(req)
    while len(req.tokens) < 13:
        eng.step()
    span = eng.config.decode_span
    assert eng.cache.owned_window(req.rid)[0] > 0   # blocks were released
    ids = np.concatenate([req.prompt, req.tokens])[:req.ctx]
    want = ref.taps_at(params, ids, layers,
                       np.arange(req.ctx - span, req.ctx), model=cfg)
    row = eng.scheduler.running.index(req)
    worst = 0.0
    for name, got in eng.step_taps.items():         # [span, 2, rows, ...]
        for j, layer in enumerate(layers):
            a, b = np.asarray(got)[:, j, row], np.asarray(want[layer][name])
            worst = max(worst, float(np.linalg.norm(a - b)
                                     / np.linalg.norm(b)))
    eng.run()
    assert eng.scheduler.audit() == []
    return worst


def test_the_decode_modules_taps_match_the_reference(tiny):
    """`step_taps`: the first routed full and window layers' router
    logits, gated attention output and routed-plus-shared output, a
    row and token step."""
    assert _taps_error(*tiny) <= 1e-5


@pytest.mark.parametrize('fault', afmoe_faults.FAULTS)
def test_a_planted_fault_shows(tiny, fault):
    """Each fault moves `forward`'s logits, or (the window's edge, a
    thing of the paged pools) what the decode module hands out, away
    from the reference's."""
    model, params, cfg = tiny
    ids = np.random.default_rng(0).integers(0, 128, (2, 40))
    want = np.asarray(ref.logits_at(
        params, ids, np.tile(np.arange(40), (2, 1)), model=cfg))
    restore = afmoe_faults.plant(fault)
    try:
        got = np.asarray(model.forward(jnp.asarray(ids)).value)
        err = np.abs(got - want).max() / np.abs(want).max()
        if fault == 'window_off_by_one_block':
            assert err <= 1e-5          # `forward` has no pools
            err = _taps_error(model, params, cfg)
    finally:
        restore()
    assert err > 1e-3, err


# -- the routed layer ---------------------------------------------------------------
def _loop(p, h, logits, bias, k, scale):
    out = np.zeros(h.shape, np.float64)
    p = {n: np.asarray(v, np.float64) for n, v in p.items()}
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    for t in range(h.shape[0]):
        top = np.argsort(-(s[t] + bias), kind='stable')[:k]
        w = scale * s[t, top] / s[t, top].sum()
        for e, we in zip(top, w):
            g = h[t] @ p['gate_proj'][e]
            a = g / (1 + np.exp(-g)) * (h[t] @ p['up_proj'][e])
            out[t] += we * (a @ p['down_proj'][e])
    return out


@pytest.mark.parametrize('grouped', [True, False])
def test_sigmoid_routing_matches_a_per_token_loop(grouped):
    rs = np.random.RandomState(0)
    p = {'gate_proj': rs.randn(8, 64, 32) * .1,
         'up_proj': rs.randn(8, 64, 32) * .1,
         'down_proj': rs.randn(8, 32, 64) * .1}
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    h, logits = rs.randn(24, 64).astype('f4'), rs.randn(24, 8).astype('f4')
    bias = (rs.randn(8) * .3).astype('f4')      # large: it moves choices
    with jax.default_matmul_precision('highest'):
        top_i, w = af.sigmoid_top_k(jnp.asarray(logits), jnp.asarray(bias),
                                    3, 2.826)
        out, stats = rw.chosen_experts(p, jnp.asarray(h), top_i, w,
                                       activation='silu', grouped=grouped)
    unbiased = np.argsort(-logits, 1, kind='stable')[:, :3]
    assert (np.sort(np.asarray(top_i), 1) != np.sort(unbiased, 1)).any()
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.826, rtol=1e-6)
    want = _loop(p, h.astype(np.float64), logits, bias, 3, 2.826)
    assert np.abs(np.asarray(out) - want).max() <= 1e-5 * np.abs(want).max()
    assert int(stats[0]) == 3 * 24


def test_the_grouped_and_the_dense_program_agree(tiny):
    """One mathematics, two programs: a prefill's sorted product and a
    decode step's dense one over the same rows and choice."""
    _, params, cfg = tiny
    p = {k[len('model.layers.1.experts.'):]: v for k, v in params.items()
         if k.startswith('model.layers.1.experts.')}
    rs = np.random.RandomState(2)
    h = jnp.asarray(rs.randn(40, 64).astype('f4'))
    top_i, w = af.sigmoid_top_k(
        jnp.asarray(rs.randn(40, 8).astype('f4')),
        params['model.layers.1.router.bias'], 3, cfg['route_scale'])
    both = [rw.chosen_experts(p, h, top_i, w, activation='silu',
                              grouped=g) for g in (True, False)]
    np.testing.assert_allclose(np.asarray(both[0][0]),
                               np.asarray(both[1][0]), rtol=1e-4, atol=1e-6)
    assert np.array_equal(np.asarray(both[0][1]), np.asarray(both[1][1]))


# -- the routed decoder without a shared expert, before and after the split ---------------
def _routed_experts_before_the_split(p, h2, logits, k, *, grouped,
                                     active=None):
    """`routed_window.routed_experts` as PR 34 left it (scoring, ReLU
    and the mixing in one function), over that module's helpers."""
    F32 = jnp.float32
    T, E = logits.shape
    wg, wu, wd = p['gate_proj'], p['up_proj'], p['down_proj']
    top_v, top_i = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(top_v.astype(F32), axis=-1)
    hit = jnp.zeros((T, E), jnp.int32).at[
        jnp.arange(T)[:, None], top_i].set(1)
    if active is not None:
        hit = hit * active.astype(jnp.int32)[:, None]
    load = hit.sum(0)
    stats = jnp.stack([load.sum(), (load > 0).sum(), load.max()])
    x = h2.astype(wg.dtype)
    if grouped:
        if active is not None:
            top_i = jnp.where(active[:, None], top_i, E)
        flat = top_i.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        rows = x[order // k]
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        g = jax.lax.ragged_dot(rows, wg, sizes, preferred_element_type=F32)
        u = jax.lax.ragged_dot(rows, wu, sizes, preferred_element_type=F32)
        y = jax.lax.ragged_dot((jax.nn.relu(g) * u).astype(wd.dtype), wd,
                               sizes, preferred_element_type=F32)
        y = y[jnp.argsort(order)].reshape(T, k, -1)
        out = (y * w[:, :, None]).sum(1)
        if active is not None:
            out = jnp.where(active[:, None], out, 0.0)
        return out, stats
    mix = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], top_i].set(w)
    g = jnp.matmul(x[None], wg, preferred_element_type=F32)
    u = jnp.matmul(x[None], wu, preferred_element_type=F32)
    y = jnp.einsum('etf,efh->eth', (jax.nn.relu(g) * u).astype(wd.dtype),
                   wd, preferred_element_type=F32)
    out = jnp.einsum('te,eth->th', mix, y,
                     precision=jax.lax.Precision.HIGHEST)
    return out, stats


def test_the_routed_window_decoders_numbers_did_not_move(monkeypatch):
    """`routed_window_tiny`'s logits through `forward`, a prefill short
    of its bucket (the grouped program, pad rows) and a decode step
    (the dense program), with the routed layer as it is now and as it
    was before it was split: bit for bit."""
    from paddle_tpu.serving.kv_cache import PrefillKV
    paddle.seed(0)
    model = rw.routed_window_tiny()
    params, _ = model.functional_state()
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 32)))
    lengths = jnp.asarray([21, 32], jnp.int32)
    eng = ServingEngine(model, ServeConfig(**SERVE))

    def numbers():
        caches = [PrefillKV(lengths=lengths) for _ in range(4)]
        logits, views = jax.jit(model.prefill)(params, None, ids, 0, caches)
        reqs = [Request(f'r{i}', np.asarray(ids[i, :n]), 12, arrival_t=0.0)
                for i, n in enumerate((21, 32))]
        eng._modules.clear()
        eng.run(reqs)
        return ([np.asarray(model.forward(ids).value), np.asarray(logits)]
                + [np.asarray(v.k) for v in views],
                [list(r.tokens) for r in reqs])

    now, tokens_now = numbers()
    monkeypatch.setattr(rw, 'routed_experts',
                        _routed_experts_before_the_split)
    before, tokens_before = numbers()
    assert tokens_now == tokens_before
    for a, b in zip(now, before):
        assert np.array_equal(a, b)


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
