#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Run from the root of a checkout, on a machine with a TPU, with no
arguments:

    python3 chip_smoke.py

One process drives the main path once at the full width of GPT-2 small
(h768 / 12 layers / 12 heads / V50304, random weights from a seed):

  device    refuse unless jax's default backend is 'tpu' and Pallas
            interpret mode is off; print versions and cache placement
  kernels   each Pallas kernel on the GPT path compiled by Mosaic and
            compared with the jnp reference in its own file
  trainer   ParallelTrainer.step on a fixed [8, 1024] batch, AMP O2,
            AdamW: loss finite and falling, Pallas custom calls present
            in the compiled step
  server    ServingEngine at serve_setup()'s config behind
            ServingFrontend on loopback: HTTP requests over both prompt
            buckets, streamed and unstreamed; no compile after warm-up,
            empty audit, greedy tokens agree with the dense path
  4 chips   (when the machine has >= 4 devices) the trainer leg again
            under a dp2 x tp2 mesh

Any failed check raises: the exit code is non-zero and no result line
is printed.  Timings and memory are printed as information, never as a
claim.  On success the last line of stdout is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
import gc
import http.client
import json
import os
import re
import sys
import time

# Agreement between a bf16 kernel and its f32 reference, as the largest
# absolute error over the largest absolute reference value: bf16 keeps
# 8 significant bits (2^-8 = 0.4% per rounding), and kernel and
# reference each round their output once.
BF16_TOL = 2e-2
F32_TOL = 1e-4
# Engine-vs-dense agreement on logits.  TPU f32 matmuls default to bf16
# passes, so two correct programs of different shapes may differ in the
# last bits; at random init the logits are O(1) and the top-2 gap
# averages ~0.1, so 0.05 separates rounding from a wrong token.
LOGIT_TOL = 5e-2
# dp2 x tp2 loss against the one-chip loss, per step, relative
MESH_LOSS_TOL = 2e-2

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 6
SERVE_PROMPT_LENS = (24, 32, 48, 64, 20, 30, 40, 60)
SERVE_NEW_TOKENS = 16


def serve_setup():
    """The server leg's model and engine config: GPT-2 small in eval
    mode, 64 slots of continuous batching over two prompt and two batch
    buckets, greedy.  SERVE_PROMPT_LENS cover both prompt buckets and
    fit max_model_len with SERVE_NEW_TOKENS."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_small
    from paddle_tpu.serving import ServeConfig

    paddle.seed(0)
    model = gpt_small(max_seq_len=256, dropout=0.0)
    model.eval()
    cfg = ServeConfig(block_size=16, max_slots=64, decode_span=8,
                      prompt_buckets=(32, 64), batch_buckets=(8, 64),
                      max_model_len=160, temperature=0.0)
    return model, cfg


FLASH_KERNELS = ('flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv')
LN_KERNEL = 'layer_norm_fwd'


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


def refuse(why):
    print(f'chip_smoke: refusing to run: {why}', file=sys.stderr,
          flush=True)
    sys.exit(2)


def agree(label, got, want, tol):
    """Largest absolute error over the largest absolute reference value
    must be within tol."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape,
          f'{label}: shape {got.shape} != {want.shape}')
    check(np.isfinite(got).all(), f'{label}: non-finite values')
    err = float(np.abs(got - want).max()
                / max(float(np.abs(want).max()), 1e-6))
    say(f'kernel {label}: err {err:.2e} (tol {tol:.0e})')
    check(err <= tol, f'{label}: err {err} > {tol}')


def has_kernel(hlo_text, name):
    """A Mosaic-compiled pallas_call named `name` is in this HLO: a
    tpu_custom_call whose op_name carries the kernel's name, as in
    "jit(train_step)/.../jvp(flash_fwd)/pallas_call"."""
    pat = re.compile(r'custom_call_target="tpu_custom_call".*'
                     rf'op_name="[^"]*\b{name}\b[^"]*/pallas_call"')
    return any(pat.search(line) for line in hlo_text.splitlines())


def peak_hbm(device):
    return (device.memory_stats() or {}).get('peak_bytes_in_use')


def count_xla_cache_events():
    """Count jax's own persistent-cache hits and misses from here on."""
    from jax import monitoring
    counts = {'hits': 0, 'misses': 0}

    def on_event(event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            counts['hits'] += 1
        elif event == '/jax/compilation_cache/cache_misses':
            counts['misses'] += 1

    monitoring.register_event_listener(on_event)
    return counts


# -- device -------------------------------------------------------------------

def leg_device():
    if os.environ.get('PADDLE_TPU_PALLAS_INTERPRET'):
        refuse('PADDLE_TPU_PALLAS_INTERPRET is set; interpret mode is a '
               'CPU test switch and proves nothing about the chip')
    import jax
    backend = jax.default_backend()
    if backend != 'tpu':
        refuse(f"jax.default_backend() is {backend!r}, not 'tpu' "
               '(no accelerator found)')
    try:
        import paddle_tpu  # noqa: F401
    except ImportError as e:
        refuse(f'paddle_tpu is not importable from {os.getcwd()}: {e}')
    import jaxlib
    from importlib import metadata
    from paddle_tpu.core import compile_cache
    from paddle_tpu.io import native
    dev = jax.devices()[0]
    say(f'device: platform={dev.platform} kind={dev.device_kind} '
        f'count={len(jax.devices())}')
    say(f'versions: jax={jax.__version__} jaxlib={jaxlib.__version__} '
        f'libtpu={metadata.version("libtpu")}')
    say(f'compile cache: jax persistent cache at '
        f'{compile_cache.setup_xla_cache()} '
        f'({compile_cache.XLA_ENV_VAR}='
        f'{os.environ.get(compile_cache.XLA_ENV_VAR)!r}); exec/text '
        f'tiers at {compile_cache.cache_dir()} '
        f'({compile_cache.ENV_VAR}='
        f'{os.environ.get(compile_cache.ENV_VAR)!r})')
    say('PADDLE_TPU_PALLAS_INTERPRET: unset')
    say(f'native loader available: {native.available()} '
        '(False means the DataLoader falls back to Python queues)')
    return {'platform': dev.platform, 'kind': dev.device_kind,
            'count': len(jax.devices())}


# -- kernels ------------------------------------------------------------------

def run_compiled(fn, args, kernels):
    """Compile fn for the chip, require every named Pallas kernel in
    the compiled HLO as a Mosaic custom call, run it."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    for name in kernels:
        check(has_kernel(text, name),
              f'{name} is not a tpu_custom_call in the compiled HLO')
    return jax.block_until_ready(compiled(*args))


def leg_kernels():
    import importlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    ln = importlib.import_module('paddle_tpu.ops.fused_norm')
    sm = importlib.import_module('paddle_tpu.ops.fused_softmax')
    rs = np.random.RandomState(0)
    t0 = time.perf_counter()

    # flash attention, causal, at the blocks each shape resolves: this
    # leg's trainer (B*H=96, T=1024, d=64) and the benchmark's cell
    # train_seq2048 (B*H=64, T=2048, d=128)
    def fwd_bwd(attn, w):
        def f(q, k, v):
            def loss(q, k, v):
                o = attn(q, k, v)
                return jnp.sum(o.astype(jnp.float32)
                               * w.astype(jnp.float32)), o
            (_, o), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (o,) + grads
        return f

    for bh, t, d in ((96, 1024, 64), (64, 2048, 128)):
        scale = 1.0 / d ** 0.5
        bq, bk = fa._tuned_blocks(t, t, d, True)
        q, k, v, w = (jnp.asarray(rs.randn(bh, t, d), jnp.bfloat16)
                      for _ in range(4))
        got = run_compiled(
            fwd_bwd(lambda q, k, v: fa._flash(q, k, v, True, scale,
                                              bq, bk), w),
            (q, k, v), FLASH_KERNELS)
        with jax.default_matmul_precision('highest'):
            want = run_compiled(
                fwd_bwd(lambda q, k, v: fa._reference(q, k, v, True,
                                                      scale), w),
                (q, k, v), ())
        for name, g, r in zip(('out', 'dq', 'dk', 'dv'), got, want):
            agree(f'flash {name} [{bh},{t},{d}] causal blocks '
                  f'({bq},{bk})', g, r, BF16_TOL)

    # LayerNorm at the trainer's shape: [B*T, H] bf16, f32 affine
    x = jnp.asarray(rs.randn(8192, 768), jnp.bfloat16)
    gamma = jnp.asarray(1 + 0.1 * rs.randn(768), jnp.float32)
    beta = jnp.asarray(0.1 * rs.randn(768), jnp.float32)
    got = run_compiled(ln.fused_layer_norm, (x, gamma, beta),
                       (LN_KERNEL,))
    agree('layer_norm [8192,768] bf16', got,
          ln._reference(x, gamma, beta, 1e-5), BF16_TOL)

    # row softmax: attention probabilities [B, H, T, T] at T=256 take
    # the kernel; a vocabulary row is 51 MB, past VMEM, and its gate
    # must send it to XLA
    x = jnp.asarray(rs.randn(8, 12, 256, 256) * 3, jnp.float32)
    got = run_compiled(sm.fused_softmax, (x,), ('softmax_fwd',))
    agree('softmax [8,12,256,256] f32', got, sm._reference(x, None),
          F32_TOL)
    xv = jnp.asarray(rs.randn(256, 50304), jnp.float32)
    text = jax.jit(sm.fused_softmax).lower(xv).compile().as_text()
    check('tpu_custom_call' not in text,
          'vocabulary softmax [256,50304] reached a Pallas kernel')
    say('kernel softmax [256,50304] f32: gate sends it to XLA')
    return {'kernels_s': round(time.perf_counter() - t0, 1)}


# -- trainer ------------------------------------------------------------------

def train_gpt(mesh=None):
    """GPT-2 small at [8, 1024] through ParallelTrainer: returns (losses,
    compile_s, steady step ms, compiled HLO text, trainer)."""
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import gpt_small
    from paddle_tpu.parallel import ParallelTrainer

    dist_env.set_mesh(mesh)
    paddle.seed(0)
    model = gpt_small(max_seq_len=TRAIN_SEQ, dropout=0.0,
                      fused_head=True, fused_head_chunks=8)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs['use_pure_fp16'] = True      # O2: pure bf16
    trainer = ParallelTrainer(model, opt,
                              lambda out, y: model.loss(out, y),
                              strategy=strategy, mesh=mesh)
    ids = np.random.RandomState(0).randint(
        0, model.config.vocab_size,
        size=(TRAIN_BATCH, TRAIN_SEQ)).astype('int64')
    losses, times = [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        loss = float(np.asarray(jax.block_until_ready(
            trainer.step(ids, ids))))
        times.append(time.perf_counter() - t0)
        losses.append(loss)
    check(all(np.isfinite(losses)), f'non-finite loss: {losses}')
    check(losses[-1] < losses[0], f'loss did not fall: {losses}')
    steady = sorted(times[1:])[len(times[1:]) // 2]
    return (losses, times[0] - steady, steady * 1e3,
            trainer.compiled_text(), trainer)


def leg_trainer():
    import jax
    losses, compile_s, step_ms, text, _trainer = train_gpt()
    for name in FLASH_KERNELS + (LN_KERNEL,):
        check(has_kernel(text, name),
              f'{name} is not in the compiled train step: attention or '
              'LayerNorm took another path')
    n_calls = text.count('custom_call_target="tpu_custom_call"')
    say(f'trainer: losses {[round(v, 4) for v in losses]}')
    say(f'trainer: {n_calls} Pallas custom calls in the compiled step '
        f'({", ".join(FLASH_KERNELS + (LN_KERNEL,))} present)')
    return {'train_losses': losses,
            'train_compile_s': round(compile_s, 1),
            'train_step_ms': round(step_ms, 1),
            'train_tokens_per_s': round(
                TRAIN_BATCH * TRAIN_SEQ / step_ms * 1e3),
            'peak_hbm_bytes_after_trainer': peak_hbm(jax.devices()[0])}


# -- server -------------------------------------------------------------------

def post_generate(port, doc):
    """POST /v1/generate; returns (tokens, final state) for both the
    SSE stream and the one-document form."""
    conn = http.client.HTTPConnection('127.0.0.1', port, timeout=300)
    try:
        conn.request('POST', '/v1/generate', body=json.dumps(doc),
                     headers={'Content-Type': 'application/json',
                              'Connection': 'close'})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f'/v1/generate -> {resp.status}: '
                               f'{resp.read()[:200]!r}')
        if not doc['stream']:
            out = json.loads(resp.read())
            return out['tokens'], out['state']
        tokens = []
        while True:
            line = resp.readline()
            check(line, 'stream ended without a terminal event')
            if not line.startswith(b'data: '):
                continue
            ev = json.loads(line[len(b'data: '):])
            if ev.get('done'):
                check(ev['n'] == len(tokens), f'stream lost tokens: {ev}')
                return tokens, ev['state']
            check(ev['i'] == len(tokens), f'stream out of order: {ev}')
            tokens.append(ev['token'])
    finally:
        conn.close()


def leg_server():
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.jit import functional_call
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.frontend import ServingFrontend

    dist_env.set_mesh(None)
    model, cfg = serve_setup()
    eng = ServingEngine(model, cfg)
    t0 = time.perf_counter()
    eng.warmup()
    warmup_s = time.perf_counter() - t0
    compiled = eng.compile_count
    say(f'server: warm-up built {compiled} modules in {warmup_s:.1f}s '
        f'(prompt buckets {cfg.prompt_buckets}, batch buckets '
        f'{cfg.batch_buckets})')
    buckets = {eng.prompt_bucket(n) for n in SERVE_PROMPT_LENS}
    check(buckets == set(cfg.prompt_buckets),
          f'prompts cover buckets {buckets}, not {cfg.prompt_buckets}')

    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, model.config.vocab_size, size=n).tolist()
               for n in SERVE_PROMPT_LENS]
    fe = ServingFrontend(eng, port=0).start()
    try:
        t0 = time.perf_counter()
        results = [post_generate(fe.port, {
            'prompt': p, 'max_new_tokens': SERVE_NEW_TOKENS,
            'stream': i % 2 == 0}) for i, p in enumerate(prompts)]
        wall = time.perf_counter() - t0
        health = http.client.HTTPConnection('127.0.0.1', fe.port,
                                            timeout=30)
        health.request('GET', '/healthz',
                       headers={'Connection': 'close'})
        check(json.loads(health.getresponse().read())['ok'],
              '/healthz not ok after serving')
        health.close()
    finally:
        fe.stop()
    for i, (tokens, state) in enumerate(results):
        check(state == 'done', f'request {i} ended {state!r}')
        check(len(tokens) == SERVE_NEW_TOKENS,
              f'request {i}: {len(tokens)} tokens')
    check(eng.compile_count == compiled,
          f'compiled after warm-up: {compiled} -> {eng.compile_count}')
    audit = eng.scheduler.audit()
    check(not audit, f'scheduler/KV audit: {audit}')
    say(f'server: {len(results)} HTTP requests finished, no compile '
        'after warm-up, audit empty')

    # Agreement with the dense path, for request 0.  (a) Under a full
    # forward of prompt+tokens (no KV cache, XLA attention) every token
    # the engine chose is within LOGIT_TOL of the best logit at its
    # position.  (b) model.generate (dense KV cache) emits the same
    # tokens up to the first position that is a near tie by (a)'s
    # logits.
    prompt, eng_tokens = prompts[0], results[0][0]
    ids = np.asarray([prompt + eng_tokens[:-1]], 'int64')
    params, buffers = model.functional_state()
    logits = jax.jit(lambda p, b, x: functional_call(
        model, p, b, (x,), training=False)[0])(params, buffers, ids)
    dense = np.asarray(logits, np.float32)[0, len(prompt) - 1:]
    gaps = dense.max(-1) - dense[np.arange(len(eng_tokens)), eng_tokens]
    say(f'server: engine tokens vs dense forward: worst logit gap '
        f'{gaps.max():.2e} (tol {LOGIT_TOL:.0e})')
    check(gaps.max() <= LOGIT_TOL,
          f'engine token off the dense argmax by {gaps.max()}')
    gen = model.generate(paddle.to_tensor(np.asarray([prompt], 'int64')),
                         max_new_tokens=SERVE_NEW_TOKENS, temperature=0)
    gen_tokens = np.asarray(gen.value)[0, len(prompt):].tolist()
    same = next((i for i, (a, b) in enumerate(zip(eng_tokens, gen_tokens))
                 if a != b), len(eng_tokens))
    if same < len(eng_tokens):
        top2 = np.sort(dense[same])[-2:]
        check(top2[1] - top2[0] <= LOGIT_TOL,
              f'engine and generate diverge at token {same} where the '
              f'dense top-2 margin is {top2[1] - top2[0]}')
    say(f'server: engine and model.generate agree on the first {same} '
        f'of {len(eng_tokens)} greedy tokens')
    return {'serve_warmup_s': round(warmup_s, 1),
            'serve_modules': compiled,
            'serve_decoded_tokens_per_s': round(
                len(results) * SERVE_NEW_TOKENS / wall, 1),
            'serve_tokens_request0': eng_tokens,
            'peak_hbm_bytes_after_server': peak_hbm(jax.devices()[0])}


# -- four chips ---------------------------------------------------------------

def leg_four_chips(one_chip_losses):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from paddle_tpu.distributed import env as dist_env

    mesh = dist_env.build_mesh({'dp': 2, 'tp': 2})
    try:
        losses, compile_s, step_ms, text, trainer = train_gpt(mesh)
    finally:
        dist_env.set_mesh(None)
    # under a mesh attention rides flash_attention_spmd (shard_map over
    # dp/tp); LayerNorm is the partitioner's, by the gate's design
    for name in FLASH_KERNELS:
        check(has_kernel(text, name),
              f'{name} is not in the dp2 x tp2 step: attention did not '
              'go through flash_attention_spmd')
    mesh_devices = set(mesh.devices.flat)
    sharded = 0
    for name, arr in trainer.params.items():
        want = trainer._sharding_for(name, arr)
        check(isinstance(arr.sharding, NamedSharding)
              and arr.sharding.is_equivalent_to(want, arr.ndim),
              f'{name}: sharding {arr.sharding} != declared {want}')
        where = want.devices_indices_map(arr.shape)
        check({s.device for s in arr.addressable_shards} == mesh_devices,
              f'{name}: shards are not on the mesh devices')
        for shard in arr.addressable_shards:
            check(shard.index == where[shard.device],
                  f'{name}: device {shard.device} holds {shard.index}, '
                  f'its PartitionSpec says {where[shard.device]}')
        sharded += any(ax is not None for ax in want.spec)
    in_use = [(d.memory_stats() or {}).get('bytes_in_use', 0)
              for d in mesh.devices.flat]
    check(all(b > 0 for b in in_use), f'HBM in use per device: {in_use}')
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(losses, one_chip_losses))
    say(f'four chips: {len(trainer.params)} parameters on their '
        f'declared devices ({sharded} sharded), HBM in use {in_use}')
    say(f'four chips: losses {[round(v, 4) for v in losses]}, worst '
        f'relative gap to one chip {worst:.2e} (tol {MESH_LOSS_TOL:.0e})')
    check(worst <= MESH_LOSS_TOL,
          f'dp2 x tp2 losses {losses} vs one chip {one_chip_losses}')
    return {'mesh_losses': losses,
            'mesh_compile_s': round(compile_s, 1),
            'mesh_step_ms': round(step_ms, 1),
            'mesh_peak_hbm_bytes': [peak_hbm(d)
                                    for d in mesh.devices.flat]}


def main():
    t_start = time.perf_counter()
    device = leg_device()
    from paddle_tpu.core import compile_cache
    xla_cache = count_xla_cache_events()
    info = {}
    info.update(leg_kernels())
    info.update(leg_trainer())
    gc.collect()            # the one-chip trainer's state leaves HBM
    info.update(leg_server())
    gc.collect()
    if device['count'] >= 4:
        info.update(leg_four_chips(info['train_losses']))
    else:
        say(f'four chips: not run ({device["count"]} device)')
    # a second start of the same commit must hit jax's cache; a
    # fallback_exec here would mean a warm exec-tier module failed and
    # was silently rerun cold
    say(f'jax persistent cache: {xla_cache["hits"]} hits, '
        f'{xla_cache["misses"]} misses; compile_cache.stats(): '
        f'{compile_cache.stats()}')
    info['xla_cache_hits'] = xla_cache['hits']
    info['xla_cache_misses'] = xla_cache['misses']
    info['total_s'] = round(time.perf_counter() - t_start, 1)
    say('info: ' + json.dumps(info))
    print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
    main()
