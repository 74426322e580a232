"""Runner `serve_routed_shared`: the `serve_routed` runner for a routed
decoder with a shared expert, sigmoid scores, gated attention and a
leading dense layer (`paddle_tpu/models/afmoe.py`) over the two-group
paged pool.  ServingEngine.warmup(), then run(requests, timeout_s)
inside one span, then its own report; the engine's loop is not rebuilt
here.  `build`, `reference`, `tap` and `probe` are this model's; the
clocks (`EngineClock`, `TracedClock`), the served tokens' check
(`served`), `context_positions` and `whole` are the runners' that were
there.  `run` is `runners/serve_routed.py`'s with those four as
PARAMETERS, so that one `run` can serve every serving runner once a
`benchmark` PR points the other three at it; until then it is the
fourth copy (PERF.md section 7).

correct = (a) a probe before the window and the profiler: one prompt a
prompt bucket (`bucket - 5` long), all submitted TOGETHER,
`probe.new_tokens` tokens each, so the longest decodes across the
window of 2,048 and window blocks are released under it; every token
the engine chose greedily must lie within `probe.logit_gap_tol` of the
float32 reference's best at its position (tokens are never compared
with tokens), and no more than `probe.not_best_tol` of them may lie
under it at all: a token whose eighth expert flipped in one layer is
routed otherwise in every layer behind it, so the WORST gap of a
sound program is that of another function's choice (the configuration
has the readings) and holds only garbage off; the share of tokens it
happens to is small and steady, and a lower precision or a fault
multiplies it.  (a2) One more request with the longest prompt is stepped
until the window group has released blocks under it, and what the
engine's OWN decode module, the one the window times, handed out
(`ServingEngine.step_taps`: of the first routed full and window layers
the gated attention output, of the first routed layer the
routed-plus-shared output and the router's logits, a row and token
step) is held to the reference's at those positions: the two attention
outputs within `probe.attn_rel_tol` and the layer's output within
`probe.moe_rel_tol` (relative, Euclidean), and the share of the
decoded tokens whose experts in that layer differ from the reference's
under `probe.expert_flip_tol`.  The router reads the layer's
post-attention state here, computed through bfloat16 matmuls, so an
eighth expert that stands close to the ninth does flip now and then (a
flipped expert is another function from that layer on): the flips are
counted over every decoded token and the three outputs are compared
over the tokens whose experts agree.  (b) Invariants that hold under every interleaving: every
request accounted for once, delivered tokens add up, both groups'
audits empty, every block of both groups free again.  (c) After the
window, outside every clock, a sample of what was served under load
against the reference by the same gap (`serve.served`).
"""
import importlib
import time

import numpy as np

from benchmark.runners.serve import served
from benchmark.runners.serve_routed import (TracedClock, context_positions,
                                            model_kwargs, whole)


def build(config, seed, clock):
    """The model with the benchmark's own weights loaded into it, its
    engine, and those weights as they were drawn."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    from paddle_tpu.serving import ServeConfig, ServingEngine
    dist_env.set_mesh(None)
    paddle.seed(seed)
    model = AfmoeForCausalLM(AfmoeConfig(
        dtype=config['weights_dtype'], **model_kwargs(config)))
    weights = load_weights(config, model, seed)
    engine = ServingEngine(model, ServeConfig(**config['serve']),
                           now_fn=clock)
    cache, stated = engine.cache, config['kv_pool']
    held = {'dtype': str(jnp.dtype(cache.dtype)),
            'full_blocks': cache.groups[0].num_blocks,
            'window_blocks': cache.groups[1].num_blocks,
            'window_bound': cache.window_bound,
            'bytes': cache.pool_bytes}
    differs = {k: (v, stated[k]) for k, v in held.items()
               if k in stated and stated[k] != v}
    if differs:
        raise ValueError(f'the engine\'s KV pools differ from what the '
                         f'configuration states: {differs}')
    return model, engine, weights


def load_weights(config, model, seed):
    """Draws the benchmark's weights for `seed` and loads each into
    `model` as it comes; returns them as they were drawn."""
    import paddle_tpu as paddle
    from benchmark.reference import trinity_ref
    weights = {}
    for name, w in trinity_ref.weights(config, seed):
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
    tensor; for the control with every matrix rounded to `weights_as`,
    the precision below the configuration's."""
    import functools
    from benchmark.reference import trinity_ref
    if perturb:
        weights = {k: v + np.asarray(perturb, v.dtype)
                   for k, v in weights.items()}
    return functools.partial(trinity_ref.logits_at, weights,
                             weights_as=weights_as,
                             model=model_kwargs(config))


def tap(config, engine, weights, seed, say, compared, weights_as=None):
    """The probe's direct limits (this file's header, a2), read from
    what the engine's own decode module handed out (`step_taps`).
    `weights_as` is the control's: the reference with its matrices in
    that dtype stands in the program's place, on the ids the program
    served, and the same comparison decides.  Returns ok."""
    import jax
    from paddle_tpu.serving.scheduler import Request
    from benchmark.reference import trinity_ref
    m, p = model_kwargs(config), config['probe']
    limits = {'attn_full_rel': float(p['attn_rel_tol']),
              'attn_window_rel': float(p['attn_rel_tol']),
              'moe_rel': float(p['moe_rel_tol']),
              'expert_flips': float(p['expert_flip_tol'])}
    compared.update({k: [float('inf'), v] for k, v in limits.items()})
    rng = np.random.default_rng([int(seed), 5])
    prompt = rng.integers(0, int(config['model']['published_vocab_size']),
                          size=max(engine.config.prompt_buckets) - 5,
                          dtype=np.int64)
    t0 = time.monotonic()
    steps, span = int(p['tap_after_tokens']), engine.config.decode_span
    cache, sched = engine.cache, engine.scheduler
    layers = cache.tap_layers           # (a full, a window): both routed
    routed = min(layers)                # the first routed layer
    req = Request('tap', prompt, steps + 2 * span, arrival_t=0.0)
    engine.submit(req)
    handed = []         # a decode dispatch: the row's taps [span, 2, ...]
    while not req.done and len(req.tokens) < steps:
        dispatched = engine.interventions
        engine.step()
        if engine.interventions > dispatched:
            row = sched.running.index(req)
            handed.append({k: np.asarray(v)[:, :, row] for k, v in
                           jax.device_get(engine.step_taps).items()})
    P, ctx = prompt.size, req.ctx
    if req.done or len(handed) * span != ctx - P:
        say(f'tap: the request ended {req.state}/{req.reason} with '
            f'{len(req.tokens)} tokens after {len(handed)} dispatches')
        return False
    first, blocks = cache.owned_window(req.rid)
    released = cache.counters['window_blocks_released']
    # the dispatches fed ids[P:ctx], one a token step, the last of them
    # ids[ctx - span:ctx]; a step's query sees the ids up to its own
    ids = np.concatenate([req.prompt, req.tokens])[:ctx]

    def of_reference(weights_as):
        """{layer: taps} of what the decode steps had to compute in the
        two tapped layers at every decoded position; the ids padded to
        the one length every pass of the reference has (it compiles
        its pieces a length)."""
        padded = np.zeros(engine.config.max_model_len, np.int64)
        padded[:ctx] = ids
        return trinity_ref.taps_at(weights, padded, layers,
                                   np.arange(P, ctx), model=m,
                                   weights_as=weights_as)

    want = of_reference(None)
    if weights_as is None:
        got = {layer: {k: np.concatenate([h[k][:, j] for h in handed])
                       for k in handed[0]}
               for j, layer in enumerate(layers)}
    else:
        got = of_reference(weights_as)

    def chosen(taps):
        return np.asarray(trinity_ref.chosen(
            taps[routed]['router'],
            weights[f'model.layers.{routed}.router.bias'],
            m['experts_per_token']))

    # a token whose eighth expert stands as close to the ninth as the
    # activations' rounding computes another function from that layer
    # on: such tokens are counted, and the outputs are compared over
    # the others
    agree = (chosen(got) == chosen(want)).all(-1)

    def rel(layer, name):
        a, b = (np.asarray(x[layer][name], np.float64)[agree]
                for x in (got, want))
        return float(np.nan_to_num(np.linalg.norm(a - b)
                                   / np.linalg.norm(b), nan=np.inf))

    full, window = layers
    errs = {'attn_full_rel': rel(full, 'attn'),
            'attn_window_rel': rel(window, 'attn'),
            'moe_rel': rel(routed, 'moe'),
            'expert_flips': float(1.0 - agree.mean())}
    compared.update({name: [err, limits[name]]
                     for name, err in errs.items()})
    engine.run()                                  # drain the request
    audit = sched.audit()
    say(f'tap: a prompt of {P} stepped to {ctx} positions in '
        f'{len(handed)} dispatches, window blocks held from {first} '
        f'({len(blocks)} of them, {released} released so far); '
        + ('the reference in ' + str(weights_as) if weights_as
           else 'the decode module\'s own taps')
        + f' against the reference over the {int(agree.sum())} of '
        f'{ctx - P} decoded tokens whose layer-{routed} experts are the '
        f'reference\'s, relative: gated attention of layer {full} (full) '
        f'{errs["attn_full_rel"]:.3e}, of layer {window} (window) '
        f'{errs["attn_window_rel"]:.3e} (tol {limits["attn_full_rel"]}), '
        f'routed plus shared output of layer {routed} '
        f'{errs["moe_rel"]:.3e} (tol {limits["moe_rel"]}); the share '
        f'whose experts differ {errs["expert_flips"]:.5f} (tol '
        f'{limits["expert_flips"]}); audit {audit or "empty"}, pools '
        f'whole {whole(engine)}; {time.monotonic() - t0:.1f}s')
    return bool(all(err <= limits[name] for name, err in errs.items())
                and first > 0 and not audit and whole(engine))


def probe(config, engine, weights, logits_at, seed, say, compared,
          buckets=None, tap=tap):
    """One prompt a bucket, all live together, then the reference's
    forward of prompt + tokens; then the tap.  Returns ok."""
    from paddle_tpu.serving.scheduler import Request
    from benchmark import logit_gap
    p = config['probe']
    new = int(p['new_tokens'])
    compared['probe_logit_gap'] = [float('inf'), float(p['logit_gap_tol'])]
    compared['probe_not_best'] = [float('inf'), float(p['not_best_tol'])]
    rng = np.random.default_rng([int(seed), 2])
    id_limit = int(config['model']['published_vocab_size'])
    t0 = time.monotonic()
    reqs = []
    for bucket in buckets or engine.config.prompt_buckets:
        prompt = rng.integers(0, id_limit, size=int(bucket) - 5,
                              dtype=np.int64)
        reqs.append(Request(f'probe{bucket}', prompt, new, arrival_t=0.0))
    engine.run(reqs)
    for req in reqs:
        if req.state != Request.DONE or len(req.tokens) != new:
            say(f'probe: {req.rid} ended {req.state}/{req.reason} with '
                f'{len(req.tokens)} tokens')
            return False
    say(f'probe: engine {time.monotonic() - t0:.1f}s')
    ok, gaps = logit_gap.check(
        'probe_logit_gap', logits_at,
        [(r.prompt, list(r.tokens)) for r in reqs], p['logit_gap_tol'],
        # the served tokens' width, so the reference compiles once
        say, compared, width=engine.config.max_model_len,
        keep=new, block=1, id_limit=id_limit,
        what='prompts, one a bucket, live together')
    # the worst gap is a flipped expert's (this file's header): the
    # share of the tokens that are not the reference's best is what
    # tells the program from a lower precision and from most faults
    not_best = float((gaps > 0).mean()) if gaps is not None else np.inf
    compared['probe_not_best'][0] = not_best
    audit = engine.scheduler.audit()
    say(f'probe: {not_best:.4f} of the tokens are not the reference\'s '
        f'best (tol {p["not_best_tol"]}); audit {audit or "empty"}, pools '
        f'whole {whole(engine)}')
    tap_ok = tap(config, engine, weights, seed, say, compared)
    return bool(ok and not_best <= float(p['not_best_tol']) and tap_ok
                and not audit and whole(engine))


def run(cell, seed, seconds, trace_on, t_start, say,
        clock=time.monotonic, reference_perturb=0.0, *, build=build,
        reference=reference, probe=probe, tap=tap):
    import functools
    import jax
    from paddle_tpu.serving.scheduler import Request
    from benchmark import harness, logit_gap
    config, traffic = cell['config'], cell['traffic']
    compiles = harness.CompileCounter()
    eclock = TracedClock(clock)
    t0 = time.monotonic()
    _model, engine, weights = build(config, seed, eclock)
    logits_at = reference(config, weights, reference_perturb)
    t1 = time.monotonic()
    engine.warmup()
    t2 = time.monotonic()
    say(f'model and engine {t1 - t0:.1f}s, warm-up of '
        f'{engine.compile_count} modules {t2 - t1:.1f}s')
    # every module has run once beside the weights and the pools: the
    # engine's own peak, before the reference allocates anything
    peak_hbm_bytes = harness.device_info()['memory_peak_bytes']
    compared = {}
    probe_ok = probe(config, engine, weights, logits_at, seed, say,
                     compared, tap=tap)
    requests = importlib.import_module(
        'benchmark.generators.' + traffic['generator']).make(
            traffic, seed, seconds)
    say(f'set-up compile cache: {compiles.hits} hits, {compiles.misses} '
        f'misses of {compiles.built} programs')
    compiled_before = compiles.built
    modules_before = engine.compile_count
    finished_before = len(engine.scheduler.finished)

    def counted(now):
        return {'t': now, 'interventions': engine.interventions,
                'decoded_tokens': engine.decoded_tokens,
                'preempted': engine.scheduler.counters.get(
                    'preempted', 0),
                'token_steps': engine.scheduler.counters.get(
                    'decode_steps', 0),
                'kv_blocks_read': engine.kv_blocks_read,
                'prefills': engine._prefills,
                **engine.counts()}

    if trace_on:
        eclock.tracer = harness.TraceWindow(cell['name'])
    eclock.counted = counted
    eclock.last, eclock.gaps = None, []
    t_window = time.monotonic()
    setup_s = t_window - t_start
    before = counted(clock())
    eclock.trace_at = before['t'] + 0.4 * seconds
    with jax.profiler.TraceAnnotation('bench.engine_run'):
        report = engine.run(requests,
                            timeout_s=seconds + float(traffic['drain_s']))
    tracer = eclock.tracer
    # only the profiler's stalls inside run() are part of its wall time
    stall_s = tracer.stall_s if tracer else 0.0
    if tracer is not None and tracer.open:
        tracer.stop()
    eclock.tracer = None
    wall_s = report['wall_s'] - stall_s
    # a backlog due all at once is the same queue whatever held the
    # engine up: the per-layer counters are of the whole of run()
    upto = counted(before['t'] + wall_s)

    # -- what happened to each request --------------------------------------
    vocab = int(config['model']['vocab_size'])
    by_rid = {}
    for req in engine.scheduler.finished[finished_before:]:
        by_rid.setdefault(req.rid, []).append(req)
    cut_is_failure = float(traffic['drain_s']) > 0
    attempted = failed = done = cut = 0
    for req in requests:
        unended = req.reason == 'engine_timeout' \
            or req.rid not in by_rid        # never left the generator
        if unended and not cut_is_failure:
            cut += 1            # still queued or running at the cut
            continue
        attempted += 1
        if (req.state == Request.DONE
                and len(req.tokens) == req.max_new_tokens
                and all(0 <= t < vocab for t in req.tokens)):
            done += 1
        else:
            failed += 1
    accounted = (set(by_rid) <= {r.rid for r in requests}
                 and all(len(v) == 1 for v in by_rid.values())
                 and all(r.rid in by_rid or not r.tokens
                         for r in requests)
                 and attempted == done + failed
                 and attempted + cut == len(requests))
    delivered = sum(len(r.tokens) for r in requests)
    tokens_add_up = delivered == report['decoded_tokens']
    audit = report['audit']
    cache = engine.cache
    say(f'window: {len(requests)} offered, {done} done, {failed} failed, '
        f'{cut} cut; {report["decoded_tokens"]} tokens in {wall_s:.3f}s; '
        f'accounted {accounted}, tokens add up {tokens_add_up} '
        f'({delivered}), audit {audit or "empty"}, pools whole '
        f'{whole(engine)}; paged kernel {report.get("paged_kernel")}; '
        'most blocks ever held, full group '
        f'{cache.groups[0].high_water_blocks} of '
        f'{cache.groups[0].num_blocks - 1}, window group '
        f'{cache.groups[1].high_water_blocks} of '
        f'{cache.groups[1].num_blocks - 1}')
    say('host: the longest times between two readings of the engine\'s '
        'clock (an intervention is one or more), s at s into the window: '
        + ', '.join(f'{gap:.3f} at {at - before["t"]:.1f}'
                    for gap, at in eclock.gaps)
        + f'; the window began at {before["t"]:.2f} s of time.monotonic()')

    # before the served tokens' reference compiles its own shapes
    compiles_in_window = (compiles.built - compiled_before) \
        + (engine.compile_count - modules_before)
    served_ok = served(
        config, traffic, logit_gap.sample(
            requests, seed, config['probe']['served_requests']),
        logits_at, say, compared)
    say(f'allocator peak: {peak_hbm_bytes} B after warm-up (the engine '
        f'alone), {harness.device_info()["memory_peak_bytes"]} B at the '
        'end (with the reference)')
    delta = {k: upto[k] - before[k] for k in upto}
    say('counters of the window: ' + ', '.join(
        f'{k} {v}' for k, v in sorted(delta.items()) if k != 't'))
    positions = functools.partial(context_positions, engine, requests)
    traced = None
    if tracer is not None and eclock.before_trace:
        lo, hi = eclock.before_trace, eclock.after_trace or upto
        traced = {k: hi[k] - lo[k] for k in lo if k != 't'}
        traced['prefill_window_keys'] = positions(
            lo['t'] - engine._epoch,
            hi['t'] - engine._epoch)['prefill_window']
        say(f'traced: the profiler held the engine {stall_s:.1f}s; '
            f'between its start and stop {hi["t"] - lo["t"]:.2f}s: '
            + ', '.join(f'{k} {v}' for k, v in sorted(traced.items())))
    return {
        'compared': dict(
            compared,
            requests_unaccounted=[0 if accounted else 1, 0],
            tokens_not_adding_up=[abs(delivered
                                      - report['decoded_tokens']), 0],
            audit_findings=[len(audit), 0],
            pool_blocks_missing=[cache.num_blocks - 1
                                 - cache.free_blocks, 0]),
        'correct': bool(probe_ok and served_ok and accounted
                        and tokens_add_up and not audit
                        and whole(engine)),
        'attempted': attempted, 'failed': failed,
        'end_to_end': {'setup_s': (setup_s, 's'),
                       'serve_tokens_per_s': (
                           report['decoded_tokens'] / wall_s, 'tokens/s')},
        'counters': {
            **{k: v for k, v in delta.items() if k != 't'},
            'window_ms': delta['t'] * 1e3,
            'decode_lanes': delta['interventions']
            * engine.config.decode_span * engine.config.max_slots,
            'preemptions': delta['preempted'],
            'compiles_in_window': compiles_in_window,
            'peak_hbm_bytes': peak_hbm_bytes,
            # the live rows' contexts, for the operations attention did
            'context_positions': positions(),
            'traced': traced,
        },
        'trace': tracer.load() if tracer else None,
    }
