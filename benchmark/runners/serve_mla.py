"""Runner `serve_mla`: `serve_routed_shared.run` for a latent-attention
decoder with sigmoid-routed experts of which it holds a share
(`paddle_tpu/models/joyai.py`) over the paged latent pool
(`LatentKVCache`).  ServingEngine.warmup(), then run(requests,
timeout_s) inside one span, then its own report; `run` is that
runner's, handed this model's `build`, `reference` and `tap` (its
`probe` as it is: one prompt a bucket, the gap and the share of tokens
under the reference's best, then the tap), so no fifth copy of `run`
exists.

correct = (a) the probe (serve_routed_shared.py's header, a): one
prompt a prompt bucket, `bucket - 5` long, all submitted TOGETHER,
`probe.new_tokens` each, every greedy token within
`probe.logit_gap_tol` of the float32 reference's best at its position
and no more than `probe.not_best_tol` of them under it; the reference
runs the EXPANDED form, so the decode steps' absorbed form is what is
checked.  (a2) One more request with the longest prompt is stepped
through `probe.tap_after_tokens` tokens of the engine's OWN decode
module, the one the window times, and what that module handed out
(`ServingEngine.step_taps`: the latent attention's output before W_o of
layer 0, the dense one, and of layer 1, the first routed one; layer 1's
routed-plus-shared output and its router's logits over every expert) is
held to the reference's at those positions: the two attention outputs
within `probe.attn_rel_tol`, the layer's output within
`probe.moe_rel_tol` (relative, Euclidean, over the tokens whose eight
experts are the reference's) and the share of tokens whose experts
differ under `probe.expert_flip_tol`.  (b) The run's invariants
(accounted, tokens add up, audit empty, pool whole).  (c) After the
window a sample of what was served under load by the same gap.
"""
import time

import numpy as np

from benchmark.runners import serve_routed_shared as shared
from benchmark.runners.serve_routed import model_kwargs, whole


def build(config, seed, clock):
    """The model with the benchmark's own weights loaded into it, its
    engine, and those weights as they were drawn."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.models.joyai import JoyAIConfig, JoyAIForCausalLM
    from paddle_tpu.serving import ServeConfig, ServingEngine
    dist_env.set_mesh(None)
    paddle.seed(seed)
    model = JoyAIForCausalLM(JoyAIConfig(
        dtype=config['weights_dtype'], **model_kwargs(config)))
    weights = load_weights(config, model, seed)
    engine = ServingEngine(model, ServeConfig(**config['serve']),
                           now_fn=clock)
    cache, stated = engine.cache, config['kv_pool']
    held = {'dtype': str(jnp.dtype(cache.dtype)),
            'num_blocks': cache.num_blocks, 'row': cache.row,
            'bytes': cache.pool_bytes}
    differs = {k: (v, stated[k]) for k, v in held.items()
               if k in stated and stated[k] != v}
    if differs:
        raise ValueError(f'the engine\'s latent pool differs from what '
                         f'the configuration states: {differs}')
    return model, engine, weights


def load_weights(config, model, seed):
    """Draws the benchmark's weights for `seed` and loads each into
    `model` as it comes; returns them as they were drawn."""
    import paddle_tpu as paddle
    from benchmark.reference import joyai_ref
    weights = {}
    for name, w in joyai_ref.weights(config, seed):
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
    from benchmark.reference import joyai_ref
    if perturb:
        weights = {k: v + np.asarray(perturb, v.dtype)
                   for k, v in weights.items()}
    return functools.partial(joyai_ref.logits_at, weights,
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
    from benchmark.reference import joyai_ref
    m, p = model_kwargs(config), config['probe']
    limits = {'attn_dense_rel': float(p['attn_rel_tol']),
              'attn_routed_rel': float(p['attn_rel_tol']),
              'moe_rel': float(p['moe_rel_tol']),
              'expert_flips': float(p['expert_flip_tol'])}
    compared.update({k: [float('inf'), v] for k, v in limits.items()})
    rng = np.random.default_rng([int(seed), 5])
    prompt = rng.integers(0, int(config['model']['published_vocab_size']),
                          size=max(engine.config.prompt_buckets) - 5,
                          dtype=np.int64)
    t0 = time.monotonic()
    steps, span = int(p['tap_after_tokens']), engine.config.decode_span
    sched = engine.scheduler
    dense, routed = engine.cache.tap_layers
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
        return joyai_ref.taps_at(weights, padded, (dense, routed),
                                 np.arange(P, ctx), model=m,
                                 weights_as=weights_as)

    want = of_reference(None)
    if weights_as is None:
        got = {layer: {k: np.concatenate([h[j][k] for h in handed])
                       for k in handed[0][j]}
               for j, layer in enumerate((dense, routed))}
    else:
        got = of_reference(weights_as)

    def chosen(taps):
        return np.asarray(joyai_ref.chosen(
            taps[routed]['router'],
            weights[f'model.layers.{routed}.router.bias'],
            m['experts_per_token']))

    # a token whose eighth expert stands as close to the ninth as the
    # activations' rounding computes another function from that layer
    # on: such tokens are counted, and the outputs compared over the
    # others (serve_routed_shared.py's header)
    agree = (chosen(got) == chosen(want)).all(-1)

    def rel(layer, name):
        a, b = (np.asarray(x[layer][name], np.float64)[agree]
                for x in (got, want))
        return float(np.nan_to_num(np.linalg.norm(a - b)
                                   / np.linalg.norm(b), nan=np.inf))

    errs = {'attn_dense_rel': rel(dense, 'attn'),
            'attn_routed_rel': rel(routed, 'attn'),
            'moe_rel': rel(routed, 'moe'),
            'expert_flips': float(1.0 - agree.mean())}
    compared.update({name: [err, limits[name]]
                     for name, err in errs.items()})
    engine.run()                                  # drain the request
    audit = sched.audit()
    say(f'tap: a prompt of {P} stepped to {ctx} positions in '
        f'{len(handed)} dispatches; '
        + ('the reference in ' + str(weights_as) if weights_as
           else 'the decode module\'s own taps')
        + f' against the reference over the {int(agree.sum())} of '
        f'{ctx - P} decoded tokens whose layer-{routed} experts are the '
        f'reference\'s, relative: latent attention of layer {dense} '
        f'{errs["attn_dense_rel"]:.3e}, of layer {routed} '
        f'{errs["attn_routed_rel"]:.3e} (tol {limits["attn_dense_rel"]}), '
        f'routed plus shared output of layer {routed} '
        f'{errs["moe_rel"]:.3e} (tol {limits["moe_rel"]}); the share '
        f'whose experts differ {errs["expert_flips"]:.5f} (tol '
        f'{limits["expert_flips"]}); audit {audit or "empty"}, pool '
        f'whole {whole(engine)}; {time.monotonic() - t0:.1f}s')
    return bool(all(err <= limits[name] for name, err in errs.items())
                and not audit and whole(engine))


def run(cell, seed, seconds, trace_on, t_start, say, clock=time.monotonic,
        reference_perturb=0.0, **parts):
    """`serve_routed_shared.run` with this model's parts; a test hands
    another part in `parts`."""
    parts = {'build': build, 'reference': reference,
             'probe': shared.probe, 'tap': tap, **parts}
    return shared.run(cell, seed, seconds, trace_on, t_start, say, clock,
                      reference_perturb, **parts)
