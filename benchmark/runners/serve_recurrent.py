"""Runner `serve_recurrent`: the `serve` runner for a model whose cache
is one recurrent state a sequence (a retention decoder) where the
`serve` runner's is the paged KV pool.  ServingEngine.warmup(), then
run(requests, timeout_s) inside one span, then its own report; the
engine's loop is not rebuilt here.  `run` below is `runners/serve.py`'s
`run` with another `build`, `reference` and `probe` and three more
counters (a copy: PERF.md section 7); the clock that opens the profiler
(`EngineClock`), the served tokens' check (`served`) and, through
`benchmark/logit_gap.py`, the gap, the sample and the decision are
shared with it.

correct = the same two parts, and a third.  (a) A probe before the
window and the profiler, on a schedule this file fixes: one prompt a
prompt bucket (`bucket - 5` long: off the bucket, so a pad position that
reached the state would show), all submitted TOGETHER, so several slots
are live at once at different depths and a state read from or written
to the wrong slot shows; `probe.new_tokens` tokens each.  Two limits.
Every token the engine chose greedily must lie within a logit gap of
the float32 reference's best at its position (tokens are never compared
with tokens): that holds the program to the mathematics, but bf16
weights put the engine's logits so far from the reference's that the
state's precision hides behind them.  So the state each request left in
its slot is held, to `probe.state_rel_tol`, against the reference's
definition of it (`state_errors`): that one sees a state that is not
float32.  (b) Invariants that hold under every interleaving: every
request accounted for once, delivered tokens add up, the scheduler's
audit empty, every slot free again.  (c) After the window, outside
every clock, a sample of what was served under load against the
reference by the same gap (`serve.served`: the longest request the
window finished and others drawn from the seed, of each the first and
last `probe.served_tokens` / 2 tokens: a state the prefill did not
overwrite shows in the first, and the gate forgets it within some
twenty tokens): a row's tokens do not depend on its batch, so this
holds under every interleaving too.

A third architecture would bring: a model file under paddle_tpu/models
with `prefill`/`decode_step` and, if its memory is neither of the two
caches, a cache class; a plain reference under benchmark/reference; and
a runner with its own `build` and `probe`.  Everything else (traffic,
generators, the engine's spans, the readers) is found by name.
"""
import importlib
import time

import numpy as np

from benchmark.runners.serve import EngineClock, served


def build(config, seed, clock):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.models.retention import (RetentionConfig,
                                             RetentionForCausalLM)
    from paddle_tpu.serving import ServeConfig, ServingEngine

    dist_env.set_mesh(None)
    paddle.seed(seed)
    model_cfg = {k: v for k, v in config['model'].items()
                 if k != 'published_vocab_size'}
    model = RetentionForCausalLM(RetentionConfig(
        dtype=config['weights_dtype'], **model_cfg))
    engine = ServingEngine(model, ServeConfig(**config['serve']),
                           now_fn=clock)
    cache, stated = engine.cache, config['state']
    held = {'dtype': str(jnp.dtype(cache.dtype)),
            'features': cache.features, 'slots': cache.slots,
            'bytes': cache.state_bytes}
    differs = {k: (v, stated[k]) for k, v in held.items()
               if k in stated and stated[k] != v}
    if differs:
        raise ValueError(f'the engine\'s state cache differs from what '
                         f'the configuration states: {differs}')
    return model, engine


def reference_kwargs(model):
    return {'num_layers': model['num_layers'],
            'num_heads': model['num_heads'],
            'num_kv_heads': model['num_kv_heads'],
            'eps': model['rms_norm_eps'], 'theta': model['rope_theta']}


def slot_of(req):
    """The device row that held the request's state (the engine notes
    it when it dispatches the prefill)."""
    return [row['slot'] for row in req.trace
            if row['stage'] == 'prefill'][-1]


def reference(config, engine, perturb=0.0):
    """`logits_at(ids, positions)` of the float32 reference over the
    engine's weights (the program's own still: PERF.md section 7); for
    the tests with `perturb` added to every tensor."""
    import functools
    from benchmark.reference import brumby_ref
    params = engine._params
    if perturb:
        params = {k: v + np.asarray(perturb, v.dtype)
                  for k, v in params.items()}
    return functools.partial(brumby_ref.logits_at, params,
                             **reference_kwargs(config['model']))


def state_errors(config, engine, reqs, seed):
    """The probe's second limit: what each request left in its slot of
    the FIRST layer's state, against the reference's definition of it.

    The first layer's k, v and log decays do not depend on any state,
    so they can be had outside the engine, from the program's own
    projections (same weights, same rounding on the way into a matmul:
    the reference's float32 k would differ from the engine's by a
    bfloat16 rounding of its input, which is the size of the fault this
    limit is there to see).  `brumby_ref.state_readout` gives, from
    them, what section 1's state holds after prompt + fed tokens, read
    by eight random vectors; the engine's held (S, z) is read by the
    same vectors through the program's feature map.  Returns the
    largest relative distance (Frobenius, a request and key/value head)
    of the numerators and of the denominators."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.decoder_parts import (matmul, project_heads,
                                                 rms_norm, sub)
    from paddle_tpu.ops import power_retention as pr
    from benchmark.reference import brumby_ref
    m = config['model']

    @jax.jit
    def first_layer(params, ids):
        p = sub(params, 'model.layers.0.')
        x = params['model.embed.weight'][ids].astype(jnp.float32)
        h = rms_norm(x, p['input_norm.weight'], m['rms_norm_eps'])
        a = sub(p, 'attn.')
        _q, k, v = project_heads(
            a, h, jnp.arange(ids.shape[1], dtype=jnp.int32)[None],
            num_heads=m['num_heads'], num_kv_heads=m['num_kv_heads'],
            head_dim=m['head_dim'], eps=m['rms_norm_eps'],
            theta=m['rope_theta'])
        g = jax.nn.log_sigmoid(matmul(h, a['g_proj.weight']))
        return k[0], v[0], g[0]

    @jax.jit
    def held(S, z, slot, r):
        f = pr.phi(r)
        hi = jax.lax.Precision.HIGHEST
        return (jnp.einsum('mD,hcD->hmc', f, S[slot].astype(jnp.float32),
                           precision=hi),
                jnp.einsum('mD,hD->hm', f, z[slot].astype(jnp.float32),
                           precision=hi))

    r = np.random.default_rng([int(seed), 3]).standard_normal(
        (8, m['head_dim'])).astype(np.float32)
    r /= np.linalg.norm(r, axis=-1, keepdims=True)
    Ss, zs = engine.cache.arrays()
    params = {k: v for k, v in engine._params.items()
              if k.startswith(('model.embed.', 'model.layers.0.'))}
    worst = [0.0, 0.0]
    for req in reqs:
        # the state has taken the prompt and every token but the last
        fed = np.concatenate([req.prompt, req.tokens[:-1]])[None]
        want = brumby_ref.state_readout(*first_layer(params, fed), r)
        have = held(Ss[0], zs[0], slot_of(req), r)
        for i, (a, b) in enumerate(zip(have, want)):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            axes = tuple(range(1, a.ndim))
            err = np.sqrt(((a - b) ** 2).sum(axes) / (b ** 2).sum(axes))
            # a distance that is no number is the worst there is
            worst[i] = max(worst[i], float(np.nan_to_num(
                err, nan=np.inf).max()))
    return worst


def probe(config, engine, logits_at, seed, say, compared):
    """One prompt a bucket, all live together, then the reference's
    forward of prompt + tokens and the states they left.  Returns ok."""
    from paddle_tpu.serving.scheduler import Request
    from benchmark import logit_gap
    m, p = config['model'], config['probe']
    new = int(p['new_tokens'])
    rng = np.random.default_rng([int(seed), 2])
    id_limit = int(m['published_vocab_size'])
    t0 = time.monotonic()
    reqs = []
    for bucket in engine.config.prompt_buckets:
        prompt = rng.integers(0, id_limit, size=int(bucket) - 5,
                              dtype=np.int64)
        reqs.append(Request(f'probe{bucket}', prompt, new, arrival_t=0.0))
    engine.run(reqs)
    for req in reqs:
        if req.state != Request.DONE or len(req.tokens) != new:
            say(f'probe: {req.rid} ended {req.state}/{req.reason} with '
                f'{len(req.tokens)} tokens')
            return False
    t1 = time.monotonic()
    # nothing has been admitted since: the slots hold what the probe left
    num_err, den_err = state_errors(config, engine, reqs, seed)
    say(f'probe: engine {t1 - t0:.1f}s, states '
        f'{time.monotonic() - t1:.1f}s')
    ok, gaps = logit_gap.check(
        'probe_logit_gap', logits_at,
        [(r.prompt, list(r.tokens)) for r in reqs], p['logit_gap_tol'],
        say, compared, width=max(r.prompt.size for r in reqs) + new,
        keep=new, block=1, id_limit=id_limit,
        what='prompts, one a bucket, live together')
    audit = engine.scheduler.audit()
    whole = engine.cache.free_blocks == engine.cache.slots
    say(f'probe: worst logit gap in the last quarter of the tokens '
        f'{gaps[:, -(new // 4):].max():.4f}; held state of layer 0 '
        f'against its definition: numerators {num_err:.3e}, '
        f'denominators {den_err:.3e} (tol {p["state_rel_tol"]}); audit '
        f'{audit or "empty"}, slots free again {whole}')
    compared.update({
        'state_numerator_rel': [num_err, float(p['state_rel_tol'])],
        'state_denominator_rel': [den_err, float(p['state_rel_tol'])]})
    return bool(ok and num_err <= p['state_rel_tol']
                and den_err <= p['state_rel_tol']
                and not audit and whole)


def run(cell, seed, seconds, trace_on, t_start, say,
        clock=time.monotonic, reference_perturb=0.0):
    import jax
    from paddle_tpu.serving.scheduler import Request
    from benchmark import harness, logit_gap
    config, traffic = cell['config'], cell['traffic']
    compiles = harness.CompileCounter()
    eclock = EngineClock(clock)
    t0 = time.monotonic()
    _model, engine = build(config, seed, eclock)
    logits_at = reference(config, engine, reference_perturb)
    t1 = time.monotonic()
    engine.warmup()
    t2 = time.monotonic()
    say(f'model and engine {t1 - t0:.1f}s, warm-up of '
        f'{engine.compile_count} modules {t2 - t1:.1f}s')
    # every module has run once beside the weights and the state: the
    # engine's own peak, before the reference allocates anything
    peak_hbm_bytes = harness.device_info()['memory_peak_bytes']
    compared = {}
    probe_ok = probe(config, engine, logits_at, seed, say, compared)
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
                'state_rows_updated': engine.state_rows_updated,
                'token_steps': engine.scheduler.counters.get(
                    'decode_steps', 0)}

    if trace_on:
        eclock.tracer = harness.TraceWindow(cell['name'])
    eclock.counted = counted
    arrivals_in_window = requests[-1].arrival_t > 0
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
    # the per-layer counters: the whole of run(), or in a traced run
    # with arrivals in its window the part before the profiler started
    early = arrivals_in_window and eclock.before_trace
    upto = early or counted(before['t'] + wall_s)

    # -- what happened to each request --------------------------------------
    vocab = int(config['model']['vocab_size'])
    by_rid = {}
    for req in engine.scheduler.finished[finished_before:]:
        by_rid.setdefault(req.rid, []).append(req)
    cut_is_failure = float(traffic['drain_s']) > 0
    attempted = failed = done = cut = 0
    ttft_ms, tpot_ms, early_ttft_ms = [], [], []
    end_t = max((r.finish_t for rs in by_rid.values() for r in rs),
                default=0.0)
    for req in requests:
        unended = req.reason == 'engine_timeout' \
            or req.rid not in by_rid        # never left the generator
        if unended and not cut_is_failure:
            cut += 1            # still queued or running at the cut
            continue
        attempted += 1
        good = (req.state == Request.DONE
                and len(req.tokens) == req.max_new_tokens
                and all(0 <= t < vocab for t in req.tokens))
        if good:
            done += 1
            ttft_ms.append((req.first_token_t - req.arrival_t) * 1e3)
            if not early \
                    or req.first_token_t <= early['t'] - engine._epoch:
                early_ttft_ms.append(ttft_ms[-1])
            tpot_ms.append((req.finish_t - req.first_token_t)
                           / (len(req.tokens) - 1) * 1e3)
        else:
            failed += 1         # counts as the worst in both tails
            waited = (end_t - req.arrival_t) * 1e3
            ttft_ms.append(waited)
            tpot_ms.append(waited)
    accounted = (set(by_rid) <= {r.rid for r in requests}
                 and all(len(v) == 1 for v in by_rid.values())
                 and all(r.rid in by_rid or not r.tokens
                         for r in requests)
                 and attempted == done + failed
                 and attempted + cut == len(requests))
    delivered = sum(len(r.tokens) for r in requests)
    tokens_add_up = delivered == report['decoded_tokens']
    audit = report['audit']
    whole = engine.cache.free_blocks == engine.cache.slots
    say(f'window: {len(requests)} offered, {done} done, {failed} failed, '
        f'{cut} cut; {report["decoded_tokens"]} tokens in {wall_s:.3f}s; '
        f'accounted {accounted}, tokens add up {tokens_add_up} '
        f'({delivered}), audit {audit or "empty"}, slots free again '
        f'{whole}; state kernel {report["state_kernel"]}, '
        f'{report["state_rows_updated"]} row updates in '
        f'{report["token_steps"]} token steps')
    if ttft_ms:
        pct = harness.percentile
        say(f'tails (ms): ttft p50 {pct(ttft_ms, .5):.1f} p95 '
            f'{pct(ttft_ms, .95):.1f} max {max(ttft_ms):.1f}; tpot p50 '
            f'{pct(tpot_ms, .5):.2f} p95 {pct(tpot_ms, .95):.2f} max '
            f'{max(tpot_ms):.2f}')

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
    interventions = upto['interventions'] - before['interventions']
    decoded = upto['decoded_tokens'] - before['decoded_tokens']
    if tracer is not None:
        say(f'traced: the profiler held the engine {stall_s:.1f}s; '
            f'per-layer counters are of {"the first" if early else "all"} '
            f'{upto["t"] - before["t"]:.1f}s: {interventions} '
            f'interventions, {decoded} tokens, {len(early_ttft_ms)} '
            'first tokens')
    end_to_end = {'setup_s': (setup_s, 's'),
                  'serve_tokens_per_s': (
                      report['decoded_tokens'] / wall_s, 'tokens/s')}
    if tpot_ms:
        end_to_end['tpot_p95_ms'] = (
            harness.percentile(tpot_ms, 0.95), 'ms')
    return {
        'compared': dict(
            compared,
            requests_unaccounted=[0 if accounted else 1, 0],
            tokens_not_adding_up=[abs(delivered
                                      - report['decoded_tokens']), 0],
            audit_findings=[len(audit), 0],
            slots_not_free=[0 if whole else 1, 0]),
        'correct': bool(probe_ok and served_ok and accounted
                        and tokens_add_up and not audit and whole),
        'attempted': attempted, 'failed': failed,
        'end_to_end': end_to_end,
        'counters': {
            'window_ms': (upto['t'] - before['t']) * 1e3,
            'interventions': interventions,
            'decoded_tokens': decoded,
            'decode_lanes': interventions * engine.config.decode_span
            * engine.config.max_slots,
            'preemptions': upto['preempted'] - before['preempted'],
            'ttft_p50_ms': harness.percentile(early_ttft_ms, 0.5)
            if early_ttft_ms else None,
            'compiles_in_window': compiles_in_window,
            'peak_hbm_bytes': peak_hbm_bytes,
            'state_rows_updated': upto['state_rows_updated']
            - before['state_rows_updated'],
            'token_steps': upto['token_steps'] - before['token_steps'],
            'state_bytes_per_row': report['state_bytes_per_row'],
        },
        'trace': tracer.load() if tracer else None,
    }
