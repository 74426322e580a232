"""Runner `serve`: ServingEngine.warmup(), then run(requests,
timeout_s) inside one span, then its own report.  The engine's loop is
not rebuilt here.

correct = (a) a probe before the window and before the profiler, with
nothing else in the engine, on a schedule this file fixes: one prompt a
prompt bucket, one after another; every token the engine chose greedily
must lie within a logit gap of the float32 reference's best at its
position (tokens are never compared with tokens: at random weights the
best logit changes on rounding); (b) since PR 30, once the window has
closed and outside every clock, the same gap over every token of a
sample, drawn from the seed, of the requests the WINDOW finished, the
longest among them (`served`): what the timed path produced at its own
batch, with its own slots and blocks in use; and (c) invariants that
hold under every interleaving: every request is accounted for once,
delivered tokens add up, the allocator's audit is empty and the pool is
whole again.  Lateness, missed limits, requests cut by the window,
compiles in the window and everything read from the trace are numbers,
never `correct`.  `compared` carries each number beside its limit.
The gap, the sample and the decision are `benchmark/logit_gap.py`'s,
shared with the `serve_recurrent` runner.

The weights are the benchmark's own (`build`): drawn from the seed by
`reference/gpt_ref.py::weights`, loaded into the program through its
`set_state_dict`, and handed to the reference as they were drawn, so a
tensor the program initialises, casts or loads wrongly shows as a gap.

What the probe can see is set by the reference's own margin between
its best and second-best logit, printed beside the gaps: a fault that
moves logits by less than the tolerance passes.  Reading the wrong
block, slot or position does not (tests/benchmark_suite breaks
paged_attention and sees `correct` turn false); a KV pool held in
bfloat16 moves logits by about a thousandth and is outside it.
"""
import importlib
import time

import numpy as np

TRACE_SECONDS = 4.0


def build(config, seed, clock):
    """The model with the benchmark's own weights loaded into it, its
    engine, and those weights as the benchmark drew them
    (`gpt_ref.weights`: from the seed, on the device, in the dtype the
    configuration serves): what the references read.  The program's
    loader keeps a tensor it is handed in its own dtype as it is, so the
    two hold the same buffers until the program changes one."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServeConfig, ServingEngine
    from benchmark.reference import gpt_ref

    dist_env.set_mesh(None)
    paddle.seed(seed)
    model_cfg = {k: v for k, v in config['model'].items()
                 if k != 'published_vocab_size'}
    model = GPTForCausalLM(GPTConfig(**model_cfg))
    if config['weights_dtype'] != 'float32':
        model.to(config['weights_dtype'])
    weights = gpt_ref.weights(
        seed, config['weights_dtype'], std=model_cfg['initializer_range'],
        **{k: model_cfg[k] for k in (
            'vocab_size', 'hidden_size', 'num_layers',
            'intermediate_size', 'max_seq_len')})
    missing, unexpected = model.set_state_dict(
        {name: paddle.to_tensor(w) for name, w in weights.items()})
    if missing or unexpected:
        raise ValueError(f'the model has no weights for {missing} and no '
                         f'tensor named {unexpected}')
    engine = ServingEngine(model, ServeConfig(**config['serve']),
                           now_fn=clock)
    pool_dtype = str(jnp.dtype(engine.cache.dtype))
    if pool_dtype != config['kv_pool']['dtype']:
        raise ValueError(f'the engine\'s KV pool is {pool_dtype}, the '
                         f'configuration states {config["kv_pool"]}')
    return model, engine, weights


def reference(config, weights, perturb=0.0, weights_as=None):
    """`logits_at(ids, positions)` of the float32 reference over the
    benchmark's `weights`; for the tests with `perturb` added to every
    tensor; for the control with every matrix rounded to `weights_as`,
    the precision below the configuration's."""
    import functools
    from benchmark.reference import gpt_ref
    m = config['model']
    if perturb:
        weights = {k: v + np.asarray(perturb, v.dtype)
                   for k, v in weights.items()}
    return functools.partial(
        gpt_ref.logits_at, weights, weights_as=weights_as,
        num_layers=m['num_layers'], num_heads=m['num_heads'],
        eps=m.get('layer_norm_epsilon', 1e-5))


def probe(config, engine, logits_at, seed, say, compared):
    """One prompt a bucket through the empty engine, then the
    reference's forward of prompt + tokens.  Returns ok."""
    from paddle_tpu.serving.scheduler import Request
    from benchmark import logit_gap
    m, p = config['model'], config['probe']
    compared['probe_logit_gap'] = [float('inf'), float(p['logit_gap_tol'])]
    new = int(p['new_tokens'])
    rng = np.random.default_rng([int(seed), 2])
    id_limit = int(m['published_vocab_size'])
    t0 = time.monotonic()
    rows = []
    for bucket in engine.config.prompt_buckets:
        # a length inside the bucket and off the block grid, so the
        # prefill's padding and the partly filled block are both read
        plen = int(bucket) - 5
        prompt = rng.integers(0, id_limit, size=plen, dtype=np.int64)
        req = Request(f'probe{bucket}', prompt, new, arrival_t=0.0)
        engine.run([req])
        if req.state != Request.DONE or len(req.tokens) != new:
            say(f'probe: bucket {bucket} ended {req.state}/{req.reason} '
                f'with {len(req.tokens)} tokens')
            return False
        rows.append((prompt, list(req.tokens)))
    say(f'probe: engine {time.monotonic() - t0:.1f}s')
    ok, _gaps = logit_gap.check(
        'probe_logit_gap', logits_at, rows, p['logit_gap_tol'], say,
        compared, width=max(len(pr) + new for pr, _ in rows), keep=new,
        block=len(rows), id_limit=id_limit,
        what='prompts, one a bucket through the empty engine')
    audit = engine.scheduler.audit()
    whole = engine.cache.free_blocks == engine.cache.num_blocks - 1
    say(f'probe: audit {audit or "empty"}, pool whole {whole}')
    return bool(ok and not audit and whole)


def served(config, traffic, rows, logits_at, say, compared, judged=None):
    """After the window, outside every clock: the tokens of `rows`, a
    sample of the requests the window finished (`logit_gap.sample`), by
    the probe's gap and limit: every token, or where the configuration
    gives `probe.served_tokens` a row's first and last half of that
    many.  A probe never reaches batch buckets above the smallest,
    slots and blocks in use by others, a slot that is used again or a
    preempted request's second prefill; these rows do.  `judged` is the
    control's: other tokens in the served ones' place.  Returns ok."""
    from benchmark import logit_gap
    p, most = config['probe'], int(traffic['new_tokens']['hi'])
    ok, _gaps = logit_gap.check(
        'served_logit_gap', logits_at, rows, p['logit_gap_tol'], say,
        compared, width=int(traffic['prompt_len']['hi']) + most,
        keep=int(p.get('served_tokens', most)), judged=judged,
        id_limit=int(config['model']['published_vocab_size']),
        what='requests the window finished')
    return ok


class EngineClock:
    """The clock handed to the engine as now_fn.  In a traced run it
    also opens and closes the profiler at fixed times: the engine reads
    its clock between interventions, so the profiler starts and stops
    on the engine's own thread.  `counted` is called just before the
    profiler starts and its result kept in `before_trace`: starting and
    stopping the profiler holds the engine for seconds, and where
    requests arrive during the window the queue that leaves behind
    distorts the rest of it, so such a traced run counts its per-layer
    numbers up to that moment.  (A backlog due all at once is the same
    queue whatever held the engine up: it counts the whole of run().)"""

    def __init__(self, base):
        self.base = base
        self.tracer = None
        self.trace_at = None
        self.counted = None
        self.before_trace = None
        self.last = None
        self.gaps = []      # the longest times between two readings

    def __call__(self):
        now = self.base()
        # the engine reads its clock several times an intervention: a
        # long time between two readings is a stall inside one
        if self.last is not None and (
                len(self.gaps) < 3 or now - self.last > self.gaps[-1][0]):
            self.gaps = sorted(self.gaps + [(now - self.last, self.last)],
                               reverse=True)[:3]
        self.last = now
        tr = self.tracer
        if tr is not None and self.trace_at is not None \
                and not tr.done:
            if not tr.open and now >= self.trace_at:
                self.before_trace = self.counted(now)
                tr.start()
            elif tr.open and now >= self.trace_at + TRACE_SECONDS \
                    + tr.stall_s:
                tr.stop()
        return now


def run(cell, seed, seconds, trace_on, t_start, say,
        clock=time.monotonic, reference_perturb=0.0):
    import jax
    from paddle_tpu.serving.scheduler import Request
    from benchmark import harness, logit_gap
    config, traffic = cell['config'], cell['traffic']
    compiles = harness.CompileCounter()
    eclock = EngineClock(clock)
    t0 = time.monotonic()
    _model, engine, weights = build(config, seed, eclock)
    logits_at = reference(config, weights, reference_perturb)
    t1 = time.monotonic()
    engine.warmup()
    t2 = time.monotonic()
    say(f'model and engine {t1 - t0:.1f}s, warm-up of '
        f'{engine.compile_count} modules {t2 - t1:.1f}s')
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
                    'preempted', 0)}

    if trace_on:
        eclock.tracer = harness.TraceWindow(cell['name'])
    eclock.counted = counted
    arrivals_in_window = requests[-1].arrival_t > 0
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
    # the per-layer counters: the whole of run(), or in a traced run
    # with arrivals in its window the part before the profiler started
    # (first tokens read until then are on the engine's clock, which
    # starts at its epoch)
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
    whole = engine.cache.free_blocks == engine.cache.num_blocks - 1
    say(f'window: {len(requests)} offered, {done} done, {failed} failed, '
        f'{cut} cut; {report["decoded_tokens"]} tokens in {wall_s:.3f}s; '
        f'accounted {accounted}, tokens add up {tokens_add_up} '
        f'({delivered}), audit {audit or "empty"}, pool whole {whole}; '
        f'kv_read_share {report.get("kv_read_share")}, paged kernel '
        f'{report.get("paged_kernel")}')
    say('host: the longest times between two readings of the engine\'s '
        'clock (an intervention is one or more), s at s into the window: '
        + ', '.join(f'{gap:.3f} at {at - before["t"]:.1f}'
                    for gap, at in eclock.gaps))
    if ttft_ms:
        pct = harness.percentile
        say(f'tails (ms): ttft p50 {pct(ttft_ms, .5):.1f} p95 '
            f'{pct(ttft_ms, .95):.1f} max {max(ttft_ms):.1f}; tpot p50 '
            f'{pct(tpot_ms, .5):.2f} p95 {pct(tpot_ms, .95):.2f} max '
            f'{max(tpot_ms):.2f}')

    # read before the served tokens' reference compiles and allocates
    compiles_in_window = (compiles.built - compiled_before) \
        + (engine.compile_count - modules_before)
    device = harness.device_info()
    served_ok = served(
        config, traffic, logit_gap.sample(
            requests, seed, config['probe']['served_requests']),
        logits_at, say, compared)
    compared.update({
        'requests_unaccounted': [0 if accounted else 1, 0],
        'tokens_not_adding_up': [abs(delivered
                                     - report['decoded_tokens']), 0],
        'audit_findings': [len(audit), 0],
        'pool_blocks_missing': [engine.cache.num_blocks - 1
                                - engine.cache.free_blocks, 0]})
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
        'correct': bool(probe_ok and served_ok and accounted
                        and tokens_add_up and not audit and whole),
        'compared': compared, 'device': device,
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
            'peak_hbm_bytes': device['memory_peak_bytes'],
        },
        'trace': tracer.load() if tracer else None,
    }
