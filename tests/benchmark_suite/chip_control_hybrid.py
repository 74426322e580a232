#!/usr/bin/env python3
"""The checks of `serve_backlog_mamba_hybrid`'s `correct` that the
benchmark's own runs never make, on the chip at the cell's own size, in
ONE process (one engine; each seed's weights are drawn and loaded into
it anew; a fault's modules are traced anew beside the same arrays):

    python3 tests/benchmark_suite/chip_control_hybrid.py \\
        --seeds 1,2 --fault-seed 3 \\
        --faults conv_window_in_bfloat16,pads_reach_the_state \\
        --state-control-seed 4

One JSON line each.  `probe`: the runner's own probe on every seed (has
to pass), with `diagnosis` beside what it compared: the program's held
state and the reference's float32 device definition each against the
float64 definition the check uses, the program's distance a head, and
the state a prefill alone left (`diagnose`).  `state_control`: the
probe with every Mamba layer's state held in bfloat16, the precision
below the configuration's float32 (the arrays rounded where they lie,
and put back in float32 after): has to come out false.  `fault`: each
of `--faults` (default: hybrid_faults.FAULTS and CHIP_CONTROLS)
planted, the probe over the shortest and the longest bucket: a fault
has to come out false; a chip control says what the chip makes of a
part computed below the configuration's precision.  `layer`
(`--layer-seed`, first, before the engine is built): the first Mamba
layer's float32 part alone at the cell's widths, sound and with the
decode conv's window in bfloat16, against the float64 definition
(`layer_check`).
"""
import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

CELL = 'serve_backlog_mamba_hybrid'


def say(msg):
    print(f'[control] {msg}', file=sys.stderr, flush=True)


def _device_definition(proj, length, layer, r, model):
    """The definition as the reference computes a layer's output, in
    float32 on the device (`granite_ref`'s conv and recurrence, at the
    highest matmul precision), read by r: [H, P, m]."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import granite_ref

    @jax.jit
    def readout(proj, length, w, b, dt_bias, A_log, r):
        x, dt, A, B, _C = granite_ref._float32_part(
            proj, w, b, dt_bias, A_log, heads=model['mamba_n_heads'],
            d_state=model['mamba_d_state'])
        dt = jnp.where((jnp.arange(dt.shape[0]) < length)[:, None], dt,
                       0.0)
        _, S = granite_ref.recurrence(x, dt, A, B, B)
        return jnp.einsum('hpn,mn->hpm', S, r)

    with jax.default_matmul_precision('highest'):
        return readout(jnp.asarray(proj, jnp.float32), jnp.int32(length),
                       *(jnp.asarray(layer[k]) for k in (
                           'conv_weight', 'conv_bias', 'dt_bias', 'A_log')),
                       jnp.asarray(r, jnp.float32))


def diagnose(config, engine, weights, reqs, logged, seed):
    """Where the probe's state distance comes from: the program's held
    state and the reference's float32 device definition, each against
    the float64 definition the check uses (`program_rel`,
    `device_definition_rel`, the worst request and head), the program's
    worst request's distance a head (`program_rel_by_head`, head 0 the
    slowest to forget), and one more request of the longest prompt and
    ONE token, whose state is its prefill's alone (`prefill_only_rel`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import granite_ref
    from benchmark.runners import serve_hybrid as runner
    from paddle_tpu.ops import ssm
    from paddle_tpu.serving.scheduler import Request
    m = config['model']
    pre = 'model.layers.0.mamba.'
    layer = {k: weights[pre + k]
             for k in ('conv_weight', 'conv_bias', 'dt_bias', 'A_log')}
    r = np.random.default_rng([int(seed), 3]).standard_normal(
        (8, m['mamba_d_state'])).astype(np.float32)
    r /= np.linalg.norm(r, axis=-1, keepdims=True)

    def held(req):
        S = engine.cache.arrays()[1][0][0]
        return np.asarray(jnp.einsum(
            'hpn,mn->hpm', ssm.heads_of(S[runner.slot_of(req)],
                                        m['mamba_n_heads'])
            .astype(jnp.float32), r,
            precision=jax.lax.Precision.HIGHEST), np.float64)

    def rel(have, want):
        return np.sqrt(((have - want) ** 2).sum((1, 2))
                       / (want ** 2).sum((1, 2)))

    fed = runner.fed_projections(logged, reqs)
    out = {'program_rel': 0.0, 'device_definition_rel': 0.0}
    for req in reqs:
        seq = fed[req.rid]
        want = granite_ref.state_readout(seq, len(seq), layer, r, model=m)
        program = rel(held(req), want)
        if program.max() >= out['program_rel']:
            out['program_rel'] = float(program.max())
            out['program_rel_by_head'] = [float(f'{e:.3g}')
                                          for e in program]
        device = np.asarray(_device_definition(
            seq, len(seq), layer, r, m), np.float64)
        out['device_definition_rel'] = max(out['device_definition_rel'],
                                           float(rel(device, want).max()))
    rng = np.random.default_rng([int(seed), 7])
    req = Request('prefill_only', rng.integers(
        0, int(m['published_vocab_size']),
        size=max(engine.config.prompt_buckets) - 5, dtype=np.int64), 1,
        arrival_t=0.0)
    engine.tap_log = []
    try:
        engine.run([req])
    finally:
        log, engine.tap_log = engine.tap_log, None
    seq = runner.fed_projections(log, [req])[req.rid]
    want = granite_ref.state_readout(seq, len(seq), layer, r, model=m)
    out['prefill_only_rel'] = float(rel(held(req), want).max())
    return out


def layer_check(config, seed, length=2043, steps=63):
    """The first Mamba layer alone at the cell's widths, one row, the
    weights the benchmark draws for `seed`: in_proj of `length + steps`
    random ids (after the embedding and the input norm), then
    `ops/ssm.py`'s conv and `ssd_prefill` over the first `length`
    positions and `steps` tokens of `conv_step` and `ssm_decode`; the
    state it leaves, read by eight random vectors, against the float64
    definition fed the same projections (the worst head).  `sound`, and
    `conv_window_in_bfloat16` with each decode step's window rounded to
    bfloat16 (hybrid_faults' fault), and `prefill` the state before the
    first decode step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import granite_ref
    from paddle_tpu.models.decoder_parts import F32, matmul, rms_norm
    from paddle_tpu.ops import ssm
    m = config['model']
    H, P, N = m['mamba_n_heads'], m['mamba_d_head'], m['mamba_d_state']
    inner, conv = H * P, H * P + 2 * N
    first = m['layer_types'].index('mamba')
    pre = f'model.layers.{first}.'
    w = {}
    for name, tensor in granite_ref.weights(config, seed):
        if name == 'model.embed.weight' or name.startswith(pre):
            w[name[len(pre):] if name.startswith(pre) else name] = tensor
        elif w and name.startswith('model.layers.'):
            if int(name.split('.')[2]) > first:
                break
    rng = np.random.default_rng([int(seed), 13])
    T = length + steps
    ids = jnp.asarray(rng.integers(0, int(m['published_vocab_size']), T))
    x = w['model.embed.weight'][ids].astype(F32) * m['embedding_multiplier']
    h = rms_norm(x, w['input_norm.weight'], m['rms_norm_eps'])
    proj = matmul(h, w['mamba.in_proj.weight'])[:, inner:]
    layer = {k: w['mamba.' + k]
             for k in ('conv_weight', 'conv_bias', 'dt_bias', 'A_log')}
    r = rng.standard_normal((8, N)).astype('f4')
    r /= np.linalg.norm(r, axis=-1, keepdims=True)
    proj64 = np.asarray(proj)

    def rel(S, upto):
        want = granite_ref.state_readout(proj64, upto, layer, r, model=m)
        have = np.einsum('hpn,mn->hpm', np.asarray(ssm.heads_of(S, H),
                                                   np.float64), r)
        return float(np.sqrt(((have - want) ** 2).sum((1, 2))
                             / (want ** 2).sum((1, 2))).max())

    dt = jax.nn.softplus(proj[:, conv:] + layer['dt_bias'])
    A = -jnp.exp(layer['A_log'])
    xbc, kept = jax.jit(ssm.causal_conv1d)(
        proj[None, :length, :conv], layer['conv_weight'],
        layer['conv_bias'], jnp.array([length]))
    prefill = jax.jit(functools.partial(ssm.ssd_prefill,
                                        chunk=int(m['mamba_chunk_size'])))
    _y, S0 = prefill(xbc[..., :inner].reshape(1, length, H, P),
                     dt[None, :length], A, xbc[..., inner:inner + N],
                     xbc[..., inner + N:])
    out = {'prefill': rel(S0[0], length)}

    import hybrid_faults
    for name in ('sound', 'conv_window_in_bfloat16'):
        restore = (hybrid_faults.plant(name) if name != 'sound'
                   else (lambda: None))

        # a function of its own each time, so that jit traces it anew
        def step(S, state, p, dt_t):
            y, state = ssm.conv_step(p, state, layer['conv_weight'],
                                     layer['conv_bias'])
            _, S = ssm.ssm_decode(
                y[:, :inner].reshape(1, H, P), dt_t, A,
                y[:, inner:inner + N], y[:, inner + N:], S,
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))
            return S, state

        try:
            # two slots: the row's and one no row holds
            S, state = jnp.concatenate([S0, jnp.zeros_like(S0)]), kept
            run = jax.jit(step)
            for t in range(length, T):
                S, state = run(S, state, proj[t:t + 1, :conv],
                               dt[t:t + 1])
        finally:
            restore()
        out[name] = rel(S[0], T)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--seeds', default='')
    ap.add_argument('--fault-seed', default='')
    ap.add_argument('--faults', default='')
    ap.add_argument('--state-control-seed', default='')
    ap.add_argument('--layer-seed', default='')
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('chip_control_hybrid: no TPU, no reading')
    from paddle_tpu.core import compile_cache
    compile_cache.setup_xla_cache()
    import jax.numpy as jnp
    from benchmark import harness
    from benchmark.runners import serve_hybrid as runner
    from paddle_tpu.serving.kv_cache import RecurrentStateCache
    import hybrid_faults
    config = harness.load_cell(CELL)['config']
    if args.layer_seed:
        t0 = time.monotonic()
        print(json.dumps({'kind': 'layer', 'seed': int(args.layer_seed),
                          **layer_check(config, args.layer_seed),
                          'seconds': round(time.monotonic() - t0, 1)}),
              flush=True)
    seeds = [int(s) for s in args.seeds.split(',') if s]
    later = [int(s) for s in (args.fault_seed, args.state_control_seed)
             if s]
    if not seeds + later:
        return
    t0 = time.monotonic()
    model, engine, weights = runner.build(config, (seeds + later)[0],
                                          time.monotonic)
    engine.warmup()
    say(f'engine and warm-up {time.monotonic() - t0:.1f}s')
    loaded = (seeds + later)[0]

    def load(seed):
        nonlocal weights, loaded
        if seed != loaded:
            # let the last seed's tensors go as the new ones come: two
            # whole models do not fit beside the state and the pool
            weights.clear()
            engine._params = engine._buffers = None
            weights = runner.load_weights(config, model, seed)
            engine._params, engine._buffers = model.functional_state()
            loaded = seed
        return runner.reference(config, weights)

    def out(kind, seed, ok, compared, **more):
        print(json.dumps({'kind': kind, 'seed': seed, 'ok': bool(ok),
                          'compared': compared, **more}), flush=True)

    sound = runner.state_errors
    diagnosis = {}

    def state_errors(config, engine, weights, reqs, logged, seed):
        err = sound(config, engine, weights, reqs, logged, seed)
        diagnosis.update(diagnose(config, engine, weights, reqs, logged,
                                  seed))
        return err

    for seed in seeds:
        logits_at = load(seed)
        compared = {}
        runner.state_errors = state_errors
        try:
            ok = runner.probe(config, engine, weights, logits_at, seed, say,
                              compared)
        finally:
            runner.state_errors = sound
        out('probe', seed, ok, compared, diagnosis=dict(diagnosis))

    buckets = (min(engine.config.prompt_buckets),
               max(engine.config.prompt_buckets))

    def held_in(dtype):
        """Every state layer's arrays rounded to `dtype` where they lie,
        a layer at a time (the old arrays go as the new come), and the
        engine's modules traced anew for them."""
        states = engine.cache.state.states
        for i, layer in enumerate(states):
            states[i] = tuple(a.astype(dtype) for a in layer)
        RecurrentStateCache.dtype = dtype
        engine._modules.clear()

    if args.state_control_seed:
        seed = int(args.state_control_seed)
        logits_at = load(seed)
        t1 = time.monotonic()
        held_in(jnp.bfloat16)
        try:
            compared = {}
            ok = runner.probe(config, engine, weights, logits_at, seed, say,
                              compared, buckets=buckets)
        finally:
            held_in(jnp.float32)
        out('state_control', seed, ok, compared, state_dtype='bfloat16',
            seconds=round(time.monotonic() - t1, 1))

    if args.fault_seed:
        seed = int(args.fault_seed)
        logits_at = load(seed)
        for fault in (args.faults.split(',') if args.faults
                      else hybrid_faults.FAULTS
                      + hybrid_faults.CHIP_CONTROLS):
            t1 = time.monotonic()
            restore = hybrid_faults.plant(fault)
            engine._modules.clear()
            try:
                compared = {}
                ok = runner.probe(config, engine, weights, logits_at, seed,
                                  say, compared, buckets=buckets)
            finally:
                restore()
                engine._modules.clear()
            out('fault', seed, ok, compared, fault=fault,
                seconds=round(time.monotonic() - t1, 1))

if __name__ == '__main__':
    main()
