"""The hybrid decoder (models/granite_hybrid.py: Mamba-2 layers around a
NoPE attention layer) through ServingEngine with the hybrid cache, a
slot of state and paged blocks a sequence, on the CPU at a tiny size
(h 64, 8 Mamba heads of 16 with a state of 16, 4 query heads on 2
key/value heads of 16, chunks of 8), seeded float32 weights drawn by
benchmark/reference/granite_ref.py, against that reference (its Mamba
layers the sequential recurrence).

Tolerances: model and reference are both float32 on the CPU and differ
by the order of their additions (the chunked scan against the
recurrence), so logits agree to 1e-5 (measured 5e-8); an engine token
is judged by the gap of its reference logit to the reference's best
(tokens are never compared with tokens), which for a sound engine is
0 unless two logits tie within 1e-4.
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.granite_hybrid import granite_hybrid_tiny
from paddle_tpu.ops import _gating, ssm
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.serving.kv_cache import HybridCache
from benchmark.reference import granite_ref

GAP = 1e-4


@pytest.fixture(autouse=True)
def no_mesh():
    """One device and no mesh, whatever a test before this file left
    set: the kernels' gate takes no mesh."""
    from paddle_tpu.distributed import env as dist_env
    before = dist_env.get_mesh()
    dist_env.set_mesh(None)
    yield
    dist_env.set_mesh(before)


def _reference_model(cfg):
    return dict(vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
                layer_types=list(cfg.layer_types), num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                intermediate_size=cfg.intermediate_size,
                mamba_n_heads=cfg.mamba_n_heads,
                mamba_d_head=cfg.mamba_d_head,
                mamba_d_state=cfg.mamba_d_state,
                mamba_d_conv=cfg.mamba_d_conv,
                embedding_multiplier=cfg.embedding_multiplier,
                residual_multiplier=cfg.residual_multiplier,
                logits_scaling=cfg.logits_scaling,
                attention_multiplier=cfg.attention_multiplier,
                rms_norm_eps=cfg.rms_norm_eps,
                initializer_range=cfg.initializer_range)


def _model(seed=3, **kw):
    """The tiny model with the reference's own draw loaded into it."""
    paddle.seed(seed)
    model = granite_hybrid_tiny(**kw)
    config = {'model': _reference_model(model.config),
              'weights_dtype': model.config.dtype}
    for name, w in granite_ref.weights(config, seed):
        _, unexpected = model.set_state_dict({name: paddle.to_tensor(w)})
        assert not unexpected
    model.eval()
    return model


@pytest.fixture(scope='module')
def tiny():
    return _model()


def _engine(model, **config):
    kw = dict(max_slots=4, decode_span=4, prompt_buckets=(16, 32),
              batch_buckets=(4,), prefill_batch=2, max_model_len=64,
              temperature=0.0)
    kw.update(config)
    return ServingEngine(model, ServeConfig(**kw))


def _gaps(engine, prompt, tokens):
    """Of each chosen token, its reference logit's gap to the best; the
    ids padded to the engine's longest so the reference compiles once."""
    full = np.zeros((1, engine.config.max_model_len), np.int64)
    full[0, :len(prompt) + len(tokens) - 1] = np.concatenate(
        [prompt, tokens[:-1]])
    pos = (len(prompt) - 1 + np.arange(len(tokens)))[None]
    logits = np.asarray(granite_ref.logits_at(
        engine._params, full, pos,
        model=_reference_model(engine.model.config)))[0]
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


def _serve(engine, prompts, new_tokens):
    for i, (p, n) in enumerate(zip(prompts, new_tokens)):
        engine.submit(np.asarray(p), n, rid=f'r{i}')
    report = engine.run()
    assert report['audit'] == []
    done = {r.rid: r for r in engine.scheduler.finished}
    assert [len(done[f'r{i}'].tokens) for i in range(len(prompts))] \
        == list(new_tokens)
    return done, report


def test_the_models_forward_is_the_references(tiny):
    """Two rows of the engine's longest (the width every reference pass
    of this file has, so it compiles once)."""
    ids = np.random.RandomState(0).randint(0, 128, size=(2, 64))
    logits = np.asarray(tiny(paddle.to_tensor(ids)).value)
    params, _ = tiny.functional_state()
    ref = np.asarray(granite_ref.logits_at(
        params, ids, np.tile(np.arange(64), (2, 1)),
        model=_reference_model(tiny.config)))
    assert logits.shape == ref.shape == (2, 64, 128)
    assert np.abs(logits - ref).max() <= 1e-5


def test_the_cache_follows_from_the_model(tiny):
    eng = _engine(tiny, num_blocks=33)
    cache = eng.cache
    assert isinstance(cache, HybridCache)
    assert cache.kinds == ('state', 'kv')
    assert cache.layer_kinds == ('state', 'kv', 'state', 'state')
    (ks, vs), (Ss, convs) = cache.arrays()
    assert len(ks) == len(vs) == 1 and len(Ss) == len(convs) == 3
    assert ks[0].shape == (33, 16, 2 * 16)
    assert Ss[0].shape == (4, 16, 8 * 16) and convs[0].shape == (4, 3, 160)
    assert Ss[0].dtype == convs[0].dtype == jnp.float32
    assert cache.state_bytes == 4 * 3 * (16 * 128 + 3 * 160) * 4
    assert cache.tap_layers == (0, 1)


def test_prefill_then_decode_is_the_references_forward(tiny):
    """Prompts off the bucket (a pad position that reached a state
    would show), more requests than slots, every slot live at its own
    depth, slots reused; the states each request left are its
    definition's."""
    eng = _engine(tiny)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 128, size=n) for n in (5, 11, 27, 16, 9, 30)]
    new_tokens = [7, 12, 9, 5, 17, 20]
    done, report = _serve(eng, prompts, new_tokens)
    for i, prompt in enumerate(prompts):
        assert _gaps(eng, prompt, done[f'r{i}'].tokens).max() <= GAP
    assert report['counters'].get('preempted', 0) == 0
    assert report['state_rows_updated'] \
        == sum(new_tokens) - len(new_tokens)
    assert report['state_kernel'] is report['paged_kernel'] is False
    assert report['state_bytes'] == eng.cache.state_bytes
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    assert eng.cache.free_slots == eng.cache.slots


def test_the_logged_projections_are_the_references_and_feed_the_state(
        tiny):
    """What the engine logs of layer 0 (`tap_log`: in_proj's output of
    the conv's channels and of dt, a prefill's rows and each valid
    decode step's) is the reference's in_proj at every position a
    request fed; the state the request left in its slot is the
    definition's fed those projections."""
    from benchmark.runners.serve_hybrid import fed_projections
    eng = _engine(tiny)
    eng.tap_log = []
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, 128, size=n) for n in (5, 27, 16, 30)]
    new_tokens = [7, 12, 5, 20]
    done, _report = _serve(eng, prompts, new_tokens)
    logged, eng.tap_log = eng.tap_log, None
    kinds = {kind for kind, *_ in logged}
    assert kinds == {'prefill', 'decode'}
    fed = fed_projections(logged, list(done.values()))
    model = _reference_model(tiny.config)
    p = {k[len('model.layers.0.'):]: v for k, v in eng._params.items()
         if k.startswith('model.layers.0.')}
    layer = {k[len('mamba.'):]: v for k, v in p.items()
             if k.startswith('mamba.')}
    for rid, req in done.items():
        ids = np.concatenate([req.prompt, req.tokens[:-1]])
        assert fed[rid].shape == (ids.size, 160 + 8)
        x = np.asarray(eng._params['model.embed.weight'])[ids] * 12.0
        h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(p['input_norm.weight'])
        want = (h @ np.asarray(layer['in_proj.weight']))[:, 128:]
        np.testing.assert_allclose(fed[rid], want, rtol=1e-4, atol=1e-5)
    # the last request's layer-0 state, read back from its slot
    req = done['r3']
    slot = [row['slot'] for row in req.trace if row['stage'] == 'prefill'][-1]
    held = ssm.heads_of(eng.cache.arrays()[1][0][0][slot], 8)
    want = granite_ref.state_readout(fed['r3'], len(fed['r3']), layer,
                                     np.eye(16, dtype='f4'), model=model)
    np.testing.assert_allclose(np.asarray(held), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_a_preempted_request_frees_both_and_is_recomputed(tiny):
    """A pool of 12 blocks of 4 for four rows that grow past it: rows
    are preempted, each gives back its slot AND its blocks, and after
    re-admission its tokens are still the reference's; the audit is
    empty after every intervention and everything is free at the
    end."""
    eng = _engine(tiny, block_size=4, num_blocks=13)
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, 128, size=n) for n in (9, 14, 6, 12)]
    new_tokens = [14, 10, 16, 9]
    for i, (p, n) in enumerate(zip(prompts, new_tokens)):
        eng.submit(np.asarray(p), n, rid=f'r{i}')
    cache, sched = eng.cache, eng.scheduler
    held_at_preemption = []
    while sched.queue or sched.running or eng._in_flight is not None:
        before = sched.counters.get('preempted', 0)
        if eng.step() == 0 and eng._in_flight is None:
            eng.drain()
        assert sched.audit() == []
        if sched.counters.get('preempted', 0) > before:
            victim = sched.queue[0]
            held_at_preemption.append((cache.state.owned(victim.rid),
                                       cache.owned(victim.rid)))
    assert held_at_preemption and all(
        held == ([], []) for held in held_at_preemption)
    done = {r.rid: r for r in sched.finished}
    assert any(r.preemptions for r in done.values())
    for i, prompt in enumerate(prompts):
        assert len(done[f'r{i}'].tokens) == new_tokens[i]
        assert _gaps(eng, prompt, done[f'r{i}'].tokens).max() <= GAP
    assert cache.free_blocks == cache.num_blocks - 1
    assert cache.free_slots == cache.slots
    assert cache.audit() == []


def test_the_kernels_decode_the_tokens_the_plain_paths_do(monkeypatch):
    """At widths both kernels take (heads of 64 two to a vreg, a state
    row of 512 lanes), in interpret mode: the same greedy tokens as the
    gather and the plain update, every module on the kernels."""
    model = _model(seed=5, hidden_size=256, num_heads=4, num_kv_heads=2,
                   head_dim=64, mamba_n_heads=8, mamba_d_head=64,
                   mamba_d_state=8, layer_types=('mamba', 'attention'),
                   num_layers=2)
    prompts = [np.arange(3, 12), np.arange(40, 60)]
    paths = {}
    for interpret in (False, True):
        monkeypatch.setattr(_gating, 'INTERPRET', interpret)
        eng = _engine(model, max_slots=2, batch_buckets=(2,),
                      prompt_buckets=(32,), prefill_batch=1)
        done, report = _serve(eng, prompts, [6, 6])
        paths[interpret] = ([done[f'r{i}'].tokens for i in range(2)],
                            report['paged_kernel'], report['state_kernel'])
    assert paths[False][0] == paths[True][0]
    assert paths[False][1:] == (False, False)
    assert paths[True][1:] == (True, True)
