"""The retention decoder (models/retention.py) through ServingEngine
with the recurrent-state cache, on the CPU at a tiny size (h 64, 4
query heads on 2 key/value heads of 16, 2 layers, seeded random
float32 weights), against the plain reference
benchmark/reference/brumby_ref.py.

Tolerances: model and reference are both float32 on the CPU and differ
by the order of their additions, so logits agree to 2e-5 (measured
3e-7); an engine token is judged by the gap of its reference logit to
the reference's best (tokens are never compared with tokens), which
for a sound engine is 0 unless two logits tie within 1e-4.
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.retention import retention_tiny
from paddle_tpu.ops import _gating
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.serving.kv_cache import (PagedKVCache,
                                         RecurrentStateCache)
from paddle_tpu.serving.scheduler import Request
from benchmark.reference import brumby_ref

GAP = 1e-4


@pytest.fixture(autouse=True)
def several_chunks_a_prompt(monkeypatch):
    from paddle_tpu.ops import power_retention
    monkeypatch.setattr(power_retention, 'PREFILL_CHUNK', 8)


def _model(seed=3, **kw):
    paddle.seed(seed)
    model = retention_tiny(**kw)
    model.eval()
    return model


def _engine(model=None, **config):
    kw = dict(max_slots=4, decode_span=4, prompt_buckets=(16, 32),
              batch_buckets=(2, 4), prefill_batch=1, max_model_len=64,
              temperature=0.0)
    kw.update(config)
    return ServingEngine(model or _model(), ServeConfig(**kw))


def _ref_kwargs(cfg):
    return dict(num_layers=cfg.num_layers, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, eps=cfg.rms_norm_eps,
                theta=cfg.rope_theta)


def _gaps(engine, prompt, tokens):
    """Gap of each chosen token's reference logit to the reference's
    best, prompt + tokens through the reference's full forward."""
    full = np.concatenate([prompt, tokens[:-1]])[None]
    pos = (len(prompt) - 1 + np.arange(len(tokens)))[None]
    logits = np.asarray(brumby_ref.logits_at(
        engine._params, full, pos,
        **_ref_kwargs(engine.model.config)))[0]
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


def _serve(engine, prompts, new_tokens):
    for i, (p, n) in enumerate(zip(prompts, new_tokens)):
        engine.submit(np.asarray(p), n, rid=f'r{i}')
    report = engine.run()
    assert report['audit'] == []
    done = {r.rid: list(r.tokens) for r in engine.scheduler.finished}
    assert [len(done[f'r{i}']) for i in range(len(prompts))] \
        == list(new_tokens)
    return done, report


def test_the_models_forward_is_the_references():
    model = _model()
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 128, size=(2, 24))
    logits = np.asarray(model(paddle.to_tensor(ids)).value)
    params, _ = model.functional_state()
    ref = np.asarray(brumby_ref.logits_at(
        params, ids, np.tile(np.arange(24), (2, 1)),
        **_ref_kwargs(model.config)))
    assert logits.shape == ref.shape == (2, 24, 128)
    assert np.abs(logits - ref).max() <= 2e-5


def test_the_model_is_drawn_in_its_serving_dtype():
    """No float32 model stands anywhere: every tensor is drawn in the
    configuration's dtype by the repo's initialisers."""
    model = _model(dtype='bfloat16')
    params, buffers = model.functional_state()
    assert not buffers
    assert {str(v.dtype) for v in params.values()} == {'bfloat16'}
    head, embed = params['lm_head.weight'], params['model.embed.weight']
    assert head.shape == embed.shape == (128, 64)
    assert not np.array_equal(np.asarray(head, np.float32),
                              np.asarray(embed, np.float32))   # untied
    norm = params['model.layers.0.attn.q_norm.weight']
    assert norm.shape == (16,) and np.asarray(norm, np.float32).min() == 1


def test_the_cache_follows_from_the_model():
    from paddle_tpu.models.gpt import gpt_tiny
    eng = _engine()
    assert eng.cache.kinds == ('state',)
    assert isinstance(eng.cache, RecurrentStateCache)
    S, z = eng.cache.states[0]
    assert S.shape == (4, 2, 16, 144) and z.shape == (4, 2, 144)
    assert S.dtype == z.dtype == jnp.float32
    assert eng.cache.state_bytes == 2 * 4 * 2 * 144 * 17 * 4
    paddle.seed(0)
    gpt = ServingEngine(gpt_tiny(num_layers=1, max_seq_len=64),
                        ServeConfig(max_slots=2, max_model_len=64))
    assert gpt.cache.kinds == ('kv',) and isinstance(gpt.cache, PagedKVCache)
    assert 'state_rows_updated' not in gpt.report()


def test_prefill_then_decode_is_the_references_forward():
    """Prompts off the bucket (a pad position that reached the state
    would show), more requests than slots, every slot live at its own
    depth, slots reused."""
    eng = _engine()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, size=n) for n in (5, 11, 27, 16, 9, 30)]
    new_tokens = [7, 12, 9, 5, 17, 20]
    done, report = _serve(eng, prompts, new_tokens)
    for i, prompt in enumerate(prompts):
        assert _gaps(eng, prompt, done[f'r{i}']).max() <= GAP
    assert report['counters'].get('preempted', 0) == 0
    # every token after a request's first is one live row's update
    assert report['state_rows_updated'] \
        == sum(new_tokens) - len(new_tokens)
    assert report['state_kernel'] is False              # a CPU
    assert report['moe_kernel_prefills'] == 0           # no routed layer
    assert report['state_bytes'] == eng.cache.state_bytes
    assert report['state_bytes_per_row'] * 4 == report['state_bytes']
    assert 0 < report['state_rows_updated'] \
        <= report['token_steps'] * 4
    assert eng.cache.free_blocks == eng.cache.slots


def test_a_rows_tokens_do_not_depend_on_its_batch():
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 128, size=n) for n in (13, 29, 6)]
    alone = _engine()
    tokens, _ = _serve(alone, prompts[:1], [14])
    together = _engine()
    batch, _ = _serve(together, prompts, [14, 9, 20])
    assert _gaps(alone, prompts[0], tokens['r0']).max() <= GAP
    assert _gaps(together, prompts[0], batch['r0']).max() <= GAP
    assert batch['r0'] == tokens['r0']


def test_a_reused_slot_carries_nothing_over():
    """One slot, two requests one after the other: the second's state
    after its prefill is what an empty engine's is, bit for bit."""
    rs = np.random.RandomState(2)
    first, second = rs.randint(0, 128, size=25), rs.randint(0, 128, size=9)
    eng = _engine(max_slots=1, batch_buckets=(1,))
    _serve(eng, [first], [18])
    assert eng.cache.free_blocks == 1
    assert np.asarray(eng.cache.states[0][0]).any()     # not zeroed
    fresh = _engine(max_slots=1, batch_buckets=(1,))
    for e in (eng, fresh):
        e.submit(Request('again', second, 6))
        e.step()                                        # prefill + a span
    for (S, z), (S0, z0) in zip(eng.cache.states, fresh.cache.states):
        assert np.array_equal(np.asarray(S), np.asarray(S0))
        assert np.array_equal(np.asarray(z), np.asarray(z0))


def test_the_scheduler_never_preempts_on_this_cache():
    eng = _engine(max_slots=2, batch_buckets=(2,), max_model_len=64)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, size=30) for _ in range(5)]
    _done, report = _serve(eng, prompts, [30] * 5)
    assert report['counters'].get('preempted', 0) == 0
    assert report['counters']['completed'] == 5
    assert eng.cache.high_water_blocks == 2


class TestAudit:
    def _live(self):
        eng = _engine()
        rs = np.random.RandomState(4)
        for i in range(3):
            eng.submit(rs.randint(0, 128, size=10), 30, rid=f'r{i}')
        eng.step()
        assert eng.scheduler.audit() == []
        return eng

    def test_a_slot_with_two_owners(self):
        eng = self._live()
        eng.cache._owner['r1'] = eng.cache._owner['r0']
        assert any('aliased' in p for p in eng.scheduler.audit())

    def test_an_owner_without_a_live_request(self):
        eng = self._live()
        assert eng.cache.ensure('ghost', 1)
        assert any('ghost' in p and 'not running' in p
                   for p in eng.scheduler.audit())

    def test_a_live_request_without_a_slot(self):
        eng = self._live()
        eng.cache.free_seq('r2')
        assert any('r2' in p for p in eng.scheduler.audit())

    def test_a_leak_and_a_slot_both_free_and_owned(self):
        cache = RecurrentStateCache(1, 1, 2, 3, slots=3, max_model_len=8,
                                    device_init=False)
        assert cache.ensure('a', 5) and cache.ensure('a', 500)
        assert cache.owned('a') == [1] and cache.free_blocks == 2
        assert cache.audit() == []
        cache._free.append(1)
        assert any('both free and owned' in p for p in cache.audit())
        cache._free = [3]
        assert any('leak' in p for p in cache.audit())

    def test_ensure_takes_a_slot_once_and_fails_when_none_is_free(self):
        cache = RecurrentStateCache(1, 1, 2, 3, slots=2, max_model_len=8,
                                    device_init=False)
        assert cache.ensure('a', 1) and cache.ensure('b', 1)
        assert not cache.ensure('c', 1) and cache.owned('c') == []
        assert cache.free_seq('a') == 1 and cache.free_seq('a') == 0
        assert cache.ensure('c', 1) and cache.audit() == []


def test_padding_rows_of_a_plan_name_slots_nobody_holds():
    """The decode update rewrites each row's slot in place, so the
    rows of a plan are distinct and a padding row's is spare."""
    eng = _engine()
    rs = np.random.RandomState(5)
    for i in range(3):
        eng.submit(rs.randint(0, 128, size=10), 30, rid=f'r{i}')
    eng.step()
    plan = eng.scheduler.plan()
    assert plan.batch == 4 and len(plan.requests) == 3
    rows = eng.cache.decode_where(plan)
    held = [eng.cache.owned(f'r{i}')[0] - 1 for i in range(3)]
    assert list(rows[:3]) == held
    assert sorted(rows) == [0, 1, 2, 3]
    # a prefill's padding row is dropped: the row past the last
    where = eng.cache.prefill_where(['r0'], 2, 16)
    assert list(where) == [held[0], eng.cache.slots]


def test_the_engine_decodes_through_the_kernel_what_it_decodes_plain(
        monkeypatch):
    """head_dim 128 takes the Pallas update in interpret mode; both
    paths choose the reference's best tokens."""
    shape = dict(hidden_size=128, num_heads=2, num_kv_heads=1,
                 head_dim=128, intermediate_size=128, num_layers=1)
    rs = np.random.RandomState(6)
    prompts = [rs.randint(0, 128, size=n) for n in (5, 12, 9)]
    new_tokens = [6, 4, 7]
    tokens = {}
    for path in ('plain', 'kernel'):
        if path == 'kernel':
            monkeypatch.setattr(_gating, 'INTERPRET', True)
        eng = _engine(_model(**shape), max_slots=2, batch_buckets=(2,),
                      decode_span=2)
        tokens[path], report = _serve(eng, prompts, new_tokens)
        assert report['state_kernel'] is (path == 'kernel')
        for i, prompt in enumerate(prompts):
            assert _gaps(eng, prompt, tokens[path][f'r{i}']).max() <= GAP
    assert tokens['kernel'] == tokens['plain']


# -- a decode dispatch in flight (PR 36) ------------------------------------------------
def test_a_span_in_flight_changes_no_token_and_the_books_balance():
    """Two slots for six requests: the row that ends by count in span N
    hands its slot to the next prefill while N is still to be read, and
    the state that prefill writes there is the new request's alone:
    every request's tokens are what a one-slot engine gives."""
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, 128, size=n) for n in (9, 25, 14, 30, 5, 18)]
    new = [14, 9, 20, 6, 17, 11]
    alone = _engine(max_slots=1, batch_buckets=(1,))
    want, _ = _serve(alone, prompts, new)
    eng = _engine(max_slots=2, batch_buckets=(2,))
    got, report = _serve(eng, prompts, new)
    assert got == want
    assert report['audit'] == [] and eng._in_flight is None
    assert eng.cache.free_blocks == eng.cache.slots
    assert eng.decoded_tokens == sum(new)
    assert eng.counts()['decode_dispatches_ahead'] == eng.interventions - 1
    assert report['state_rows_updated'] == sum(new) - len(new)


def test_a_cancel_and_a_deadline_with_a_span_in_flight():
    clock = {'t': 0.0}
    eng = ServingEngine(_model(), ServeConfig(
        max_slots=4, decode_span=4, prompt_buckets=(16, 32),
        batch_buckets=(2, 4), prefill_batch=1, max_model_len=64,
        temperature=0.0), now_fn=lambda: clock['t'])
    rs = np.random.RandomState(10)
    reqs = [eng.submit(rs.randint(0, 128, size=10), 30, rid=f'r{i}',
                       deadline_s=5.0 if i == 2 else None)
            for i in range(3)]
    eng.step()
    eng.step()
    assert all(r in eng._in_flight['plan'].requests for r in reqs)
    assert eng.cancel('r0') and eng._in_flight is None
    clock['t'] = 10.0
    eng.step()
    eng.step()
    assert reqs[2].reason == 'deadline' and reqs[2].dispatched == 0
    assert eng.scheduler.audit() == []
    eng.run()
    assert reqs[1].state == Request.DONE and len(reqs[1].tokens) == 30
    assert eng.cache.free_blocks == eng.cache.slots
    assert eng.decoded_tokens == len(reqs[1].tokens) + len(reqs[2].tokens)
