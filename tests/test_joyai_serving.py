"""The latent-attention decoder with sigmoid-routed experts beside a
shared expert (models/joyai.py) at a small size on the CPU: `forward`,
and prefill then decode through `ServingEngine`'s latent pool, in every
batch bucket and across a preemption, against the plain float32
reference (benchmark/reference/joyai_ref.py, the EXPANDED form) by
logits; the absorbed decode form against the expanded one; the share
test (the parts of four disjoint held ranges add up to the uncut
layer); interleaved rotary against the pair rotation written out; what
the decode module hands out against the reference's layers; and the
eight planted faults, each of which has to show."""
import functools
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import decoder_parts as dp
from paddle_tpu.models import joyai as ja
from paddle_tpu.models import routed_window as rw
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.serving.kv_cache import (LatentKVCache, LatentCacheView,
                                         PrefillKV)
from paddle_tpu.serving.scheduler import Request
from benchmark import logit_gap
from benchmark.reference import joyai_ref as ref

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                'benchmark_suite'))
import mla_faults  # noqa: E402

SERVE = dict(block_size=4, max_slots=4, decode_span=2,
             prompt_buckets=(8, 16, 32), batch_buckets=(2, 4),
             prefill_batch=1, max_model_len=64, num_blocks=48)
SHAPES = [(5, 20), (13, 9), (30, 25), (27, 30), (8, 12), (16, 16)]
# float32 weights: the expanded and the absorbed forms differ by the
# order of float32 sums alone (some 1e-7 of a logit's spread here); a
# token the engine chose is the reference's best to within 1e-5 of a
# logit, where a fault moves logits by 1e-3 and more (the faults below)
GAP_TOL = 1e-5


def _tiny(**kw):
    """The tiny model with norms off 1 and biases off 0, as the
    benchmark draws them: a tensor that is all 1 or all 0 hides the
    fault that drops it.  Every model of one `held_experts` draws the
    same tensors.  Matrices N(0, 0.15): at 0.02 and these widths the
    scores are so small that attention is near uniform and a wrong
    softmax scale could not show."""
    paddle.seed(0)
    model = ja.joyai_tiny(initializer_range=0.15, **kw)
    rs = np.random.RandomState(1)
    model.set_state_dict({
        k: paddle.to_tensor(np.asarray(v) + 0.1 * rs.randn(*v.shape)
                            .astype('f4'))
        for k, v in model.functional_state()[0].items()
        if 'norm' in k or k.endswith('router.bias')})
    params, _ = model.functional_state()
    return model, params, dict(vars(model.config))


@pytest.fixture(scope='module')
def tiny():
    """Experts [4, 8) of 16 held: the routed layers' share."""
    return _tiny(held_experts=(4, 4))


def _ids(rows, n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (rows, n))


def test_forward_matches_the_reference(tiny):
    model, params, cfg = tiny
    ids = _ids(2, 40)
    got = np.asarray(model.forward(jnp.asarray(ids)).value)
    want = np.asarray(ref.logits_at(
        params, ids, np.tile(np.arange(40), (2, 1)), model=cfg))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_the_model_states_its_cache_and_its_share(tiny):
    model, params, _ = tiny
    assert model.serving_state == 'latent'
    assert model.cache_spec() == {'num_layers': 4, 'latent_dim': 48,
                                  'rope_dim': 16, 'tap_layers': (0, 1)}
    assert params['model.layers.1.experts.gate_proj'].shape == (4, 64, 32)
    assert params['model.layers.1.router.weight'].shape == (64, 16)
    assert params['model.layers.1.router.bias'].dtype == jnp.float32
    assert 'model.layers.0.mlp.gate_proj.weight' in params
    eng = ServingEngine(model, ServeConfig(**SERVE))
    assert isinstance(eng.cache, LatentKVCache)
    # a row: 48 of latent and 16 of rotary key, held 128 wide
    assert eng.cache.pools[0].shape == (48, 4, 128)


def _load(shapes=SHAPES):
    rng = np.random.default_rng(0)
    return [Request(f'r{i}', rng.integers(0, 256, n), new, arrival_t=0.0)
            for i, (n, new) in enumerate(shapes)]


def _gaps(params, cfg, reqs):
    gaps, _same, margin, _ = logit_gap.gaps(
        functools.partial(ref.logits_at, params, model=cfg),
        [(r.prompt, list(r.tokens)) for r in reqs], 64, 30, block=1)
    return gaps, margin


@pytest.mark.parametrize('slots', [2, 4])
def test_engine_prefill_then_decode_matches_the_reference(tiny, slots):
    """Unequal rows in each batch bucket: every served token is the
    reference's best at its position to within GAP_TOL (logits are
    compared, never tokens)."""
    model, params, cfg = tiny
    eng = ServingEngine(model, ServeConfig(**dict(
        SERVE, max_slots=slots, batch_buckets=(slots,))))
    eng.warmup()
    compiled = eng.compile_count
    reqs = _load()
    report = eng.run(reqs)
    assert eng.compile_count == compiled
    assert report['audit'] == []
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    assert all(r.state == Request.DONE for r in reqs)
    # the counts are of the routed layers' HELD experts: 4 of 16
    counts = eng.counts()
    decoded = sum(len(r.tokens) - 1 for r in reqs)
    assert 0 < counts['moe_assignments'] < decoded * 4 * 3
    assert counts['moe_experts_hit'] <= 4 * 3 * eng.interventions * 2
    gaps, margin = _gaps(params, cfg, reqs)
    assert gaps.max() <= GAP_TOL
    assert margin.min() > 0


def test_a_preempted_request_resumes_to_the_same_tokens(tiny):
    """A pool so small that rows are preempted with their span in
    flight and prefilled again: every request's tokens are what a
    one-slot engine gives, and the pool comes back whole."""
    model, params, cfg = tiny
    alone = ServingEngine(model, ServeConfig(**dict(
        SERVE, max_slots=1, batch_buckets=(1,))))
    want = _load()
    alone.run(want)
    eng = ServingEngine(model, ServeConfig(**dict(SERVE, num_blocks=30)))
    reqs = _load()
    report = eng.run(reqs)
    assert report['counters']['preempted'] >= 1
    for got, ref_req in zip(reqs, want):
        assert got.state == Request.DONE
        assert got.tokens == ref_req.tokens, got.rid
    assert report['audit'] == [] and eng._in_flight is None
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    assert report['decoded_tokens'] == sum(new for _, new in SHAPES)
    gaps, _ = _gaps(params, cfg, reqs)
    assert gaps.max() <= GAP_TOL


def test_absorbed_attention_equals_expanded_attention(tiny):
    """One layer's attention at the last position of prompts of 13 and
    27: the decode step's absorbed form over the latent pool against
    the prefill's expanded form, float32, within 1e-5."""
    model, params, cfg = tiny
    c = model.config
    a = dp.sub(params, 'model.layers.1.attn.')
    rs = np.random.RandomState(5)
    T = 28
    h = jnp.asarray(rs.randn(2, T, 64).astype('f4'))
    lens = np.asarray([13, 27])
    pos = jnp.broadcast_to(jnp.arange(T)[None], (2, T))
    with jax.default_matmul_precision('highest'):
        expanded, view = ja.attention(a, h, pos, PrefillKV(lengths=lens),
                                      c)
        # the pool as a prefill leaves it, then the last position of
        # each row written and read by a decode step
        cache = LatentKVCache(4, 48, 16, block_size=4, num_blocks=32)
        for sid, n in enumerate(lens):
            cache.ensure(sid, int(n))
        where = cache.prefill_where([0, 1], 2, T)
        pools, _ = cache.store_prefill(
            (tuple(cache.pools), ()), [view] * 4, jnp.asarray(where))
        tables = jnp.asarray(np.stack([cache.table_row(s, 8)
                                       for s in (0, 1)]))
        at = jnp.asarray(lens - 1)
        rows_h = h[jnp.arange(2), at][:, None]
        absorbed, _ = ja.attention(
            a, rows_h, at[:, None],
            LatentCacheView(pools[0], tables, at, at + 1,
                            jnp.ones(2, bool)), c)
    want = np.asarray(expanded)[np.arange(2), lens - 1]
    got = np.asarray(absorbed)[:, 0]
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize('grouped', [True, False])
def test_the_shares_add_up_to_the_uncut_layer(grouped):
    """Four chips of an expert-parallel group, each holding 4 of the 16
    experts: the routed parts their layers compute, summed, with the
    shared expert counted once, are what the reference gives for the
    whole layer with every expert held."""
    whole, params, cfg = _tiny()
    assert cfg['held_experts'] == (0, 16)
    rs = np.random.RandomState(7)
    h = jnp.asarray(rs.randn(24, 64).astype('f4'))
    with jax.default_matmul_precision('highest'):
        routed, _ = ref.routed_part(params, 2, h, model=cfg)
        want = np.asarray(routed + ref.shared_part(params, 2, h))
        logits = rw.router_logits(h, params['model.layers.2.router.weight'])
        top_i, w = rw.sigmoid_top_k(
            logits, params['model.layers.2.router.bias'], 4, 2.5)
        got, assigned = 0.0, 0
        for first in (0, 4, 8, 12):
            p = {n: params[f'model.layers.2.experts.{n}'][first:first + 4]
                 for n in ('gate_proj', 'up_proj', 'down_proj')}
            part, stats = rw.chosen_experts(
                p, h, top_i, w, activation='silu', grouped=grouped,
                held=(first, 4))
            got = got + np.asarray(part)
            assigned += int(stats[0])
        got = got + np.asarray(ja.gated_mlp(
            dp.sub(params, 'model.layers.2.shared.'), h))
    assert assigned == 24 * 4
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_interleaved_rotary_is_the_pair_rotation():
    """Lanes (2i, 2i + 1) turned by position * theta^(-2i/d), written
    out a pair at a time; the rotate-half form is untouched."""
    rs = np.random.RandomState(0)
    x = rs.randn(1, 5, 3, 8).astype('f4')
    pos = np.arange(5)[None] + 11
    got = np.asarray(dp.rotary(jnp.asarray(x), jnp.asarray(pos), 100.0,
                               interleaved=True))
    want = np.empty_like(x)
    for t in range(5):
        for i in range(4):
            a = pos[0, t] * 100.0 ** (-2 * i / 8)
            e, o = x[0, t, :, 2 * i], x[0, t, :, 2 * i + 1]
            want[0, t, :, 2 * i] = e * np.cos(a) - o * np.sin(a)
            want[0, t, :, 2 * i + 1] = e * np.sin(a) + o * np.cos(a)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    half = np.asarray(dp.rotary(jnp.asarray(x), jnp.asarray(pos), 100.0))
    assert not np.allclose(half, got, atol=1e-3)


def _taps_error(model, params, cfg):
    """The largest relative distance between what the engine's decode
    module handed out (the latent attention output of layers 0 and 1,
    layer 1's routed-plus-shared output and its router logits) and the
    reference's layers at those positions."""
    eng = ServingEngine(model, ServeConfig(**SERVE))
    layers = eng.cache.tap_layers
    req = Request('tap', _ids(1, 27, 3)[0], 20, arrival_t=0.0)
    eng.submit(req)
    while len(req.tokens) < 13:
        eng.step()
    span = eng.config.decode_span
    ids = np.concatenate([req.prompt, req.tokens])[:req.ctx]
    want = ref.taps_at(params, ids, layers,
                       np.arange(req.ctx - span, req.ctx), model=cfg)
    row = eng.scheduler.running.index(req)
    worst = 0.0
    for layer, handed in zip(layers, eng.step_taps):  # [span, rows, ...]
        for name, got in handed.items():
            a = np.asarray(got)[:, row]
            b = np.asarray(want[layer][name])
            worst = max(worst, float(np.linalg.norm(a - b)
                                     / np.linalg.norm(b)))
    assert set(eng.step_taps[1]) == {'attn', 'router', 'moe'}
    eng.run()
    assert eng.scheduler.audit() == []
    return worst


def test_the_decode_modules_taps_match_the_reference(tiny):
    assert _taps_error(*tiny) <= 1e-5


@pytest.mark.parametrize('fault', mla_faults.FAULTS)
def test_a_planted_fault_shows(tiny, fault):
    """Each fault moves `forward`'s logits or (W_UK, which only the
    decode steps' absorbed form reads) what the decode module hands out
    away from the reference's."""
    model, params, cfg = tiny
    ids = _ids(2, 40)
    want = np.asarray(ref.logits_at(
        params, ids, np.tile(np.arange(40), (2, 1)), model=cfg))
    restore = mla_faults.plant(fault)
    try:
        got = np.asarray(model.forward(jnp.asarray(ids)).value)
        err = np.abs(got - want).max() / np.abs(want).max()
        if fault == 'w_uk_transposed':
            assert err <= 1e-5          # `forward` expands
            err = _taps_error(model, params, cfg)
    finally:
        restore()
    assert err > 1e-3, err
