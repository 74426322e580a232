"""Runner `serve_hybrid`: `serve_routed_shared.run` for a decoder of
Mamba-2 state-space layers beside attention layers that carry no
positions (`paddle_tpu/models/granite_hybrid.py`) over the hybrid cache,
a slot of state and paged blocks a sequence (`HybridCache`).
ServingEngine.warmup(), then run(requests, timeout_s) inside one span,
then its own report; `run` is that runner's, handed this model's
`build`, `reference`, `probe` and `tap`, so no sixth copy of `run`
exists.

correct = (a) the probe: one prompt a prompt bucket, `bucket - 5` long
(off the bucket, so a pad that reached a state would show), all
submitted TOGETHER, `probe.new_tokens` each, every greedy token within
`probe.logit_gap_tol` of the float32 reference's best at its position
and no more than `probe.not_best_tol` of them under it; the reference's
Mamba layers run the sequential recurrence, so the chunked prefill and
the one-token update are both held to the definition.  (a3) Before
anything else is admitted, the state each probe request left in its
slot of the FIRST Mamba layer, read by random vectors, against the
definition's state fed what the program's own modules computed last in
bfloat16 for that layer (`state_errors`): the relative Frobenius
distance, the worst request and head, under `probe.state_rel_tol`: the
one limit that sees the precision of the layer's float32 part.  (a2)
One more request with the longest prompt is stepped through
`probe.tap_after_tokens` tokens of the engine's OWN decode module, the
one the window times, and what that module handed out
(`ServingEngine.step_taps`: the first Mamba layer's mixer output before
out_proj and the first attention layer's heads' output before W_o) is
held to the reference's at those positions within
`probe.mamba_rel_tol` and `probe.attn_rel_tol` (relative, Euclidean).
(b) The run's invariants (accounted, tokens add up, audit empty for
slots and blocks, every block free again; after the probe every slot
too).  (c) After the window a sample of what was served under load by
the same gap.
"""
import time

import numpy as np

from benchmark.runners import serve_routed_shared as shared
from benchmark.runners.serve_recurrent import slot_of
from benchmark.runners.serve_routed import model_kwargs
from benchmark.runners.serve_routed import whole as pool_whole


def build(config, seed, clock):
    """The model with the benchmark's own weights loaded into it, its
    engine, and those weights as they were drawn."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)
    from paddle_tpu.serving import ServeConfig, ServingEngine
    dist_env.set_mesh(None)
    paddle.seed(seed)
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        dtype=config['weights_dtype'], **model_kwargs(config)))
    weights = load_weights(config, model, seed)
    engine = ServingEngine(model, ServeConfig(**config['serve']),
                           now_fn=clock)
    cache = engine.cache
    held = {'dtype': str(jnp.dtype(cache.dtype)),
            'num_blocks': cache.kv.num_blocks, 'bytes': cache.pool_bytes,
            'state_dtype': str(jnp.dtype(cache.state.dtype)),
            'state_bytes': cache.state_bytes, 'slots': cache.slots}
    stated = {**config['kv_pool'],
              **{'state_' + k: v for k, v in config['state'].items()},
              'slots': config['state']['slots']}
    differs = {k: (v, stated[k]) for k, v in held.items()
               if k in stated and stated[k] != v}
    if differs:
        raise ValueError(f'the engine\'s pool and state differ from what '
                         f'the configuration states: {differs}')
    return model, engine, weights


def load_weights(config, model, seed):
    """Draws the benchmark's weights for `seed` and loads each into
    `model` as it comes; returns them as they were drawn."""
    import paddle_tpu as paddle
    from benchmark.reference import granite_ref
    weights = {}
    for name, w in granite_ref.weights(config, seed):
        _missing, unexpected = model.set_state_dict(
            {name: paddle.to_tensor(w)})
        if unexpected:
            raise ValueError(f'the model has no tensor named {unexpected}')
        weights[name] = w
    unloaded = set(model.functional_state()[0]) - set(weights)
    if unloaded:
        raise ValueError(f'the benchmark drew no weights for {unloaded}')
    return weights


def reference(config, weights, perturb=0.0, weights_as=None):
    """`logits_at(ids, positions)` of the float32 reference over the
    benchmark's `weights`; for the tests with `perturb` added to every
    tensor; for the control with every matrix rounded to `weights_as`."""
    import functools
    from benchmark.reference import granite_ref
    if perturb:
        weights = {k: v + np.asarray(perturb, v.dtype)
                   for k, v in weights.items()}
    return functools.partial(granite_ref.logits_at, weights,
                             weights_as=weights_as,
                             model=model_kwargs(config))


def whole(engine):
    """Every block of the pool and every slot free again."""
    return pool_whole(engine) \
        and engine.cache.free_slots == engine.cache.slots


def fed_projections(logged, reqs):
    """{rid: [positions, conv_dim + heads]}: what the first tapped
    layer's in_proj gave its conv and its dt at every position each of
    `reqs` fed, from the engine's `tap_log` `logged`: its prefill's row
    up to its length, then each decode step that was valid for its row
    (a prefill logged again, after a preemption, starts it anew)."""
    by_rid = {r.rid: r for r in reqs}
    fed = {}
    for kind, rids, valid, taps in logged:
        proj = np.asarray((taps if kind == 'prefill' else taps[0])['proj'])
        for i, rid in enumerate(rids):
            if rid not in by_rid:
                continue
            if kind == 'prefill':
                fed[rid] = [proj[i, :by_rid[rid].prompt.size]]
            else:
                fed.setdefault(rid, []).append(
                    proj[np.asarray(valid)[:, i], i])
    return {rid: np.concatenate(parts) for rid, parts in fed.items()}


def state_errors(config, engine, weights, reqs, logged, seed):
    """(a3): what each request left in its slot of the first Mamba
    layer's state, against the definition's state after the same
    positions, both read by eight random unit vectors.  The definition
    is fed what the program's own modules computed last in bfloat16 for
    that layer, in_proj's output of the conv's channels and of dt at
    every position the request fed (`fed_projections`); the conv, the
    softplus, the decays and the sequential recurrence are the
    definition's, in float64 on the host (`granite_ref.state_readout`).
    So the distance is the layer's float32 part against the
    definition, with nothing between the two that rounds to bfloat16
    or to a device's float32.  Returns the largest relative
    distance (Frobenius over head_dim and the vectors, a request and
    head); a request whose logged positions are not the ones it fed
    reads infinite."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm
    from benchmark.reference import granite_ref
    m = config['model']
    first = m['layer_types'].index('mamba')
    layer = {k: weights[f'model.layers.{first}.mamba.{k}']
             for k in ('conv_weight', 'conv_bias', 'dt_bias', 'A_log')}
    fed = fed_projections(logged, reqs)
    r = np.random.default_rng([int(seed), 3]).standard_normal(
        (8, m['mamba_d_state'])).astype(np.float32)
    r /= np.linalg.norm(r, axis=-1, keepdims=True)
    S = engine.cache.arrays()[1][0][0]   # the first state layer's S
    worst = 0.0
    for req in reqs:
        seq = fed.get(req.rid)
        if seq is None or len(seq) != req.prompt.size + len(req.tokens) - 1:
            return float('inf')
        want = granite_ref.state_readout(seq, len(seq), layer, r, model=m)
        have = np.asarray(jnp.einsum(
            'hpn,mn->hpm', ssm.heads_of(S[slot_of(req)], m['mamba_n_heads'])
            .astype(jnp.float32), r,
            precision=jax.lax.Precision.HIGHEST), np.float64)
        err = np.sqrt(((have - want) ** 2).sum((1, 2))
                      / (want ** 2).sum((1, 2)))
        # a distance that is no number is the worst there is
        worst = max(worst, float(np.nan_to_num(err, nan=np.inf).max()))
    return worst


def tap(config, engine, weights, seed, say, compared, weights_as=None):
    """The probe's direct limits (this file's header, a2), read from
    what the engine's own decode module handed out (`step_taps`).
    `weights_as` is the control's: the reference with its matrices in
    that dtype stands in the program's place, on the ids the program
    served, and the same comparison decides.  Returns ok."""
    import jax
    from paddle_tpu.serving.scheduler import Request
    from benchmark.reference import granite_ref
    m, p = model_kwargs(config), config['probe']
    limits = {'mamba_rel': float(p['mamba_rel_tol']),
              'attn_rel': float(p['attn_rel_tol'])}
    compared.update({k: [float('inf'), v] for k, v in limits.items()})
    rng = np.random.default_rng([int(seed), 5])
    prompt = rng.integers(0, int(config['model']['published_vocab_size']),
                          size=max(engine.config.prompt_buckets) - 5,
                          dtype=np.int64)
    t0 = time.monotonic()
    steps, span = int(p['tap_after_tokens']), engine.config.decode_span
    sched = engine.scheduler
    mamba, attn = engine.cache.tap_layers
    req = Request('tap', prompt, steps + 2 * span, arrival_t=0.0)
    engine.submit(req)
    handed = []         # a decode dispatch: each tapped layer's [span, ...]
    while not req.done and len(req.tokens) < steps:
        dispatched = engine.interventions
        engine.step()
        if engine.interventions > dispatched:
            row = sched.running.index(req)
            handed.append([{k: np.asarray(v)[:, row] for k, v in t.items()}
                           for t in jax.device_get(engine.step_taps)])
    P, ctx = prompt.size, req.ctx
    if req.done or len(handed) * span != ctx - P:
        say(f'tap: the request ended {req.state}/{req.reason} with '
            f'{len(req.tokens)} tokens after {len(handed)} dispatches')
        return False
    # the dispatches fed ids[P:ctx], one a token step; a step's query
    # sees the ids up to its own
    ids = np.concatenate([req.prompt, req.tokens])[:ctx]

    def of_reference(weights_as):
        """{layer: taps} the decode steps had to compute at every
        decoded position; the ids padded to the one length every pass
        of the reference has."""
        padded = np.zeros(engine.config.max_model_len, np.int64)
        padded[:ctx] = ids
        return granite_ref.taps_at(weights, padded, (mamba, attn),
                                   np.arange(P, ctx), model=m,
                                   weights_as=weights_as)

    want = of_reference(None)
    if weights_as is None:
        got = {layer: {k: np.concatenate([h[j][k] for h in handed])
                       for k in handed[0][j]}
               for j, layer in enumerate((mamba, attn))}
    else:
        got = of_reference(weights_as)

    def rel(layer, name):
        a, b = (np.asarray(x[layer][name], np.float64) for x in (got, want))
        return float(np.nan_to_num(np.linalg.norm(a - b)
                                   / np.linalg.norm(b), nan=np.inf))

    errs = {'mamba_rel': rel(mamba, 'mamba'), 'attn_rel': rel(attn, 'attn')}
    compared.update({name: [err, limits[name]]
                     for name, err in errs.items()})
    engine.run()                                  # drain the request
    audit = sched.audit()
    say(f'tap: a prompt of {P} stepped to {ctx} positions in '
        f'{len(handed)} dispatches; '
        + ('the reference in ' + str(weights_as) if weights_as
           else 'the decode module\'s own taps')
        + f' against the reference over the {ctx - P} decoded tokens, '
        f'relative: the Mamba mixer of layer {mamba} before out_proj '
        f'{errs["mamba_rel"]:.3e} (tol {limits["mamba_rel"]}), the '
        f'attention of layer {attn} {errs["attn_rel"]:.3e} (tol '
        f'{limits["attn_rel"]}); audit {audit or "empty"}, slots and '
        f'blocks whole {whole(engine)}; {time.monotonic() - t0:.1f}s')
    return bool(all(err <= limits[name] for name, err in errs.items())
                and not audit and whole(engine))


def probe(config, engine, weights, logits_at, seed, say, compared,
          tap=tap, buckets=None):
    """One prompt a bucket (of `buckets`, the engine's unless a chip
    control asks for fewer), all live together; the states they left;
    then the reference's forward of prompt + tokens; then the tap.
    Returns ok."""
    from paddle_tpu.serving.scheduler import Request
    from benchmark import logit_gap
    p = config['probe']
    new = int(p['new_tokens'])
    compared['probe_logit_gap'] = [float('inf'), float(p['logit_gap_tol'])]
    compared['probe_not_best'] = [float('inf'), float(p['not_best_tol'])]
    compared['state_rel'] = [float('inf'), float(p['state_rel_tol'])]
    rng = np.random.default_rng([int(seed), 2])
    id_limit = int(config['model']['published_vocab_size'])
    t0 = time.monotonic()
    reqs = [Request(f'probe{bucket}',
                    rng.integers(0, id_limit, size=int(bucket) - 5,
                                 dtype=np.int64), new, arrival_t=0.0)
            for bucket in buckets or engine.config.prompt_buckets]
    engine.tap_log = []
    try:
        engine.run(reqs)
    finally:
        logged, engine.tap_log = engine.tap_log, None
    for req in reqs:
        if req.state != Request.DONE or len(req.tokens) != new:
            say(f'probe: {req.rid} ended {req.state}/{req.reason} with '
                f'{len(req.tokens)} tokens')
            return False
    t1 = time.monotonic()
    # nothing has been admitted since: the slots hold what the probe left
    state_err = state_errors(config, engine, weights, reqs, logged, seed)
    compared['state_rel'][0] = state_err
    say(f'probe: engine {t1 - t0:.1f}s, states {time.monotonic() - t1:.1f}s'
        f': the held state of the first Mamba layer against its '
        f'definition {state_err:.3e} (tol {p["state_rel_tol"]})')
    ok, gaps = logit_gap.check(
        'probe_logit_gap', logits_at,
        [(r.prompt, list(r.tokens)) for r in reqs], p['logit_gap_tol'],
        # the served tokens' width, so the reference compiles once
        say, compared, width=engine.config.max_model_len,
        keep=new, block=1, id_limit=id_limit,
        what='prompts, one a bucket, live together')
    not_best = float((gaps > 0).mean()) if gaps is not None else np.inf
    compared['probe_not_best'][0] = not_best
    audit = engine.scheduler.audit()
    say(f'probe: {not_best:.4f} of the tokens are not the reference\'s '
        f'best (tol {p["not_best_tol"]}); audit {audit or "empty"}, slots '
        f'and blocks whole {whole(engine)}')
    tap_ok = tap(config, engine, weights, seed, say, compared)
    return bool(ok and not_best <= float(p['not_best_tol'])
                and state_err <= float(p['state_rel_tol']) and tap_ok
                and not audit and whole(engine))


def run(cell, seed, seconds, trace_on, t_start, say, clock=time.monotonic,
        reference_perturb=0.0, **parts):
    """`serve_routed_shared.run` with this model's parts; a test hands
    another part in `parts`.  The traffic's ids are drawn below this
    model's vocabulary: a mix written for a larger one (its `id_limit`)
    is served as it stands in every other respect."""
    parts = {'build': build, 'reference': reference, 'probe': probe,
             'tap': tap, **parts}
    return shared.run(in_vocabulary(cell), seed, seconds, trace_on, t_start,
                      say, clock, reference_perturb, **parts)


def in_vocabulary(cell):
    """`cell` with its traffic's ids drawn below the model's vocabulary
    (`id_limit` cut to it), every other parameter of the mix as it
    stands."""
    vocab = int(cell['config']['model']['published_vocab_size'])
    if int(cell['traffic']['id_limit']) <= vocab:
        return cell
    return dict(cell, traffic=dict(cell['traffic'], id_limit=vocab))
