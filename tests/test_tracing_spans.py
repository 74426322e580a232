"""PR 25's contract between the program and whoever reads its traces:

* the host spans inside ``ServingEngine.step`` / ``run`` and
  ``ParallelTrainer.step`` land, nested and in order, on one host line
  of a ``jax.profiler`` session (the same file a chip's device ops are
  written to), with telemetry NOT enabled: the session is the switch;
* every device-side scope of the list is in the lowered text of the
  tiny engine's modules and of the tiny train step, and the compiled
  text with metadata stripped is the same with and without the scopes
  (``jax.named_scope`` is metadata only).
"""
import contextlib
import os
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.serving.scheduler import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# since PR 36 a decode dispatch is kept in flight: the span of N+1 is
# sent before N's tokens are read, and the first tokens of the prefills
# just sent are read last
SERVE_CHILDREN = (
    'serve.deadlines', 'serve.admit', 'serve.prefill_dispatch',
    'serve.reserve', 'serve.plan', 'serve.decode_dispatch',
    'serve.decode_sync', 'serve.absorb', 'serve.first_token_sync',
    'serve.bookkeeping')
TRAINER_CHILDREN = ('trainer.prepare', 'trainer.dispatch', 'trainer.note')
PARENT = {**{n: 'serve.step' for n in SERVE_CHILDREN},
          **{n: 'trainer.step' for n in TRAINER_CHILDREN}}

TINY = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_seq_len=64, dropout=0.0)


def tiny_engine():
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(**TINY))
    return ServingEngine(model, ServeConfig(
        block_size=4, max_slots=4, decode_span=2, prompt_buckets=(8, 16),
        batch_buckets=(2, 4), prefill_batch=1, max_model_len=32,
        temperature=0.0))


def tiny_requests(n=3):
    rng = np.random.default_rng(3)
    # the last one is due long after the others are done, so run()
    # idles (serve.wait_arrival) however slow the machine
    return [Request(f'q{i}', rng.integers(0, 120, size=5 + i), 4,
                    arrival_t=0.02 * i + (0.75 if i == n - 1 else 0.0))
            for i in range(n)]


def tiny_trainer():
    from paddle_tpu import nn
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.parallel import ParallelTrainer
    dist_env.set_mesh(None)
    paddle.seed(7)
    lm = GPTForCausalLM(GPTConfig(fused_head=True, fused_head_chunks=2,
                                  **TINY))

    class WithLoss(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lm = lm

        def forward(self, ids, labels):
            return self.lm.loss(self.lm(ids), labels)

    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=lm.parameters())
    return ParallelTrainer(WithLoss(), opt, lambda loss: loss, n_inputs=2)


def tiny_batch():
    return np.random.default_rng(5).integers(0, 120, size=(2, 16))


# -- (b) the host spans, on the profiler's clock ------------------------------
@pytest.fixture(scope='module')
def host_lines(tmp_path_factory):
    """One jax.profiler session on the CPU around a tiny
    ServingEngine.run and two tiny ParallelTrainer.steps, everything
    compiled before it; {thread line: [(name, start, end)]}."""
    from benchmark import reduce_trace
    telemetry.disable()
    telemetry.reset()
    engine = tiny_engine()
    engine.warmup()
    trainer = tiny_trainer()
    ids = tiny_batch()
    jax.block_until_ready(trainer.step(ids, ids))
    events_before = len(telemetry.events())
    stats_before = dict(telemetry.get_recorder().span_stats)
    logdir = str(tmp_path_factory.mktemp('trace'))
    jax.profiler.start_trace(logdir)
    try:
        engine.run(tiny_requests())
        for _ in range(2):
            jax.block_until_ready(trainer.step(ids, ids))
    finally:
        jax.profiler.stop_trace()
    # the session was the only switch: no span left a record
    assert telemetry.get_recorder().span_stats == stats_before
    assert not [e for e in telemetry.events()[events_before:]
                if e['kind'] == 'span']
    trace = reduce_trace.Trace.from_file(reduce_trace.find_xplane(logdir))
    return trace.host_lines


def spans_named(lines, name):
    return [(line, s, e) for line, evs in lines.items()
            for n, s, e in evs if n == name]


def children_of(lines, line, start, end, prefix):
    return [(n, s, e) for n, s, e in lines[line]
            if n.startswith(prefix) and start <= s and e <= end
            and (s, e) != (start, end)]


@pytest.mark.parametrize('name', SERVE_CHILDREN + TRAINER_CHILDREN)
def test_span_is_a_child_of_its_step_and_in_order(host_lines, name):
    parent = PARENT[name]
    order = SERVE_CHILDREN if parent == 'serve.step' else TRAINER_CHILDREN
    parents = spans_named(host_lines, parent)
    assert parents, f'no {parent} span in the trace'
    assert len({line for line, _, _ in parents}) == 1   # one host line
    seen = 0
    for line, s, e in parents:
        kids = children_of(host_lines, line, s, e, parent.split('.')[0])
        names = [n for n, _, _ in kids]
        assert set(names) <= set(order), names          # no other names
        ranks = [order.index(n) for n in names]
        assert ranks == sorted(ranks), names            # in the order
        for (_, _, e0), (_, s1, _) in zip(kids, kids[1:]):
            assert e0 <= s1                             # siblings
        seen += names.count(name)
    assert seen, f'{name} never appeared under {parent}'


def test_every_decoding_step_has_all_its_children(host_lines):
    full = [sorted(set(n for n, _, _ in children_of(
        host_lines, line, s, e, 'serve')))
        for line, s, e in spans_named(host_lines, 'serve.step')]
    # a step that sends a span and reads the one before it
    decode_side = sorted(set(SERVE_CHILDREN) - {
        'serve.prefill_dispatch', 'serve.first_token_sync'})
    assert any(set(decode_side) <= set(names) for names in full)
    assert len(spans_named(host_lines, 'trainer.step')) == 2
    assert spans_named(host_lines, 'serve.wait_arrival')  # run()'s sleep


# -- (c) the device-side scopes ------------------------------------------------
SERVE_SCOPES = {
    'decode': ('serve.decode', 'serve.sample', 'paged.write_kv',
               'paged.gather_dense', 'paged.attention', 'gpt.embed',
               'gpt.attn', 'gpt.mlp', 'gpt.ln'),
    'prefill': ('serve.prefill', 'serve.sample', 'gpt.embed', 'gpt.attn',
                'gpt.mlp', 'gpt.ln'),
    'train': ('fused_ce.fwd', 'fused_ce.bwd', 'optimizer_update',
              'gpt.embed', 'gpt.attn', 'gpt.mlp', 'gpt.ln'),
}


def strip_metadata(text):
    """Compiled HLO text less everything a scope or a moved source
    line can touch: the metadata of each instruction, the tables of
    files, functions, locations and stack frames at the top, and the
    serial numbers in instruction names (renumbered by first
    appearance, so the same graph printed in the same order gives the
    same text whatever the uniquifier had counted before)."""
    body = re.sub(r',? ?metadata=\{(?:[^{}"]|"[^"]*")*\}', '', text)
    serial, counts = {}, {}

    def renumber(m):
        name = m.group(0)
        if name not in serial:
            stem = re.sub(r'\.\d+$', '', name)
            counts[stem] = counts.get(stem, 0) + 1
            serial[name] = f'{stem}.{counts[stem]}'
        return serial[name]

    body = re.sub(r'%[\w.\-]+', renumber, body)
    keep, skipping = [], False
    for line in body.splitlines():
        if line in ('FileNames', 'FunctionNames', 'FileLocations',
                    'StackFrames'):
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            keep.append(line)
    return '\n'.join(keep)


@contextlib.contextmanager
def no_scope(_name):
    yield


def lowered(kind):
    """The jitted module of `kind` as jax lowers it here."""
    if kind == 'train':
        trainer = tiny_trainer()
        ids = tiny_batch()
        vals = trainer._ensure_compiled((ids, ids))
        return trainer._compiled.lower(*trainer._step_example_args()[:5],
                                       *vals)
    engine = tiny_engine()
    if kind == 'decode':
        fn, _fp, example, _name, _donate = engine._decode_spec(2, 2)
    else:
        fn, _fp, example, _name, _donate = engine._prefill_spec(8, 1)
    return jax.jit(fn).lower(*example)


@pytest.fixture(scope='module')
def texts():
    """{kind: (lowered text with scopes, compiled text with, compiled
    text without)}; 'without' rebuilds the same module with
    jax.named_scope switched to a no-op."""
    from paddle_tpu.ops import fused_ce
    out = {}
    for kind in SERVE_SCOPES:
        low = lowered(kind)
        out[kind] = [low.as_text(debug_info=True),
                     low.compile().as_text()]
    patch = pytest.MonkeyPatch()
    try:
        patch.setattr(jax, 'named_scope', no_scope)
        # decorated where the module was imported: take the scope off
        patch.setattr(fused_ce, '_scan_core',
                      fused_ce._scan_core.__wrapped__)
        patch.setattr(fused_ce, '_bwd_core',
                      fused_ce._bwd_core.__wrapped__)
        for kind in SERVE_SCOPES:
            low = lowered(kind)
            assert 'gpt.attn' not in low.as_text(debug_info=True)
            out[kind].append(low.compile().as_text())
    finally:
        patch.undo()
    return out


@pytest.mark.parametrize('kind,scope', [
    (k, s) for k, scopes in SERVE_SCOPES.items() for s in scopes])
def test_lowered_text_carries_the_scope(texts, kind, scope):
    # a name of its own in a location's path: "jit(f)/gpt.attn/add"
    # in the text jax lowers, "transpose(jvp(gpt.attn))/..." backward
    rx = r'["/(]' + re.escape(scope) + r'["/)]'
    assert re.search(rx, texts[kind][0])
    assert re.search(rx, texts[kind][1])     # and survives XLA


@pytest.mark.parametrize('kind', sorted(SERVE_SCOPES))
def test_scopes_change_no_instruction(texts, kind):
    _low, with_scopes, without = texts[kind]
    assert 'op_name' in with_scopes
    assert strip_metadata(with_scopes) == strip_metadata(without)
    assert 'metadata' not in strip_metadata(with_scopes)


# -- the caches: what keys see a scope -----------------------------------------
def test_program_fingerprint_does_not_see_a_scope():
    """core.compile_cache's own fingerprints hash the jaxpr's text,
    which prints no name stack: a scope re-keys nothing there."""
    import jax.numpy as jnp
    from paddle_tpu.core import compile_cache as cc

    def scoped(x):
        with jax.named_scope('gpt.attn'):
            return jnp.sin(x) * 2

    def plain(x):
        return jnp.sin(x) * 2

    x = jnp.ones(3)
    assert cc.jaxpr_text(scoped, x) == cc.jaxpr_text(plain, x)
    assert 'gpt.attn' not in cc.jaxpr_text(scoped, x)


SCOPE_CHILD = r'''
import contextlib, sys
import jax, jax.numpy as jnp
from jax import monitoring
from paddle_tpu.core import compile_cache as cc
cc.setup_xla_cache()
seen = []
monitoring.register_event_listener(lambda e, **k: seen.append(e))
scope = jax.named_scope('gpt.attn') if sys.argv[1] == 'scoped' \
    else contextlib.nullcontext()
def f(x):
    with scope: return jnp.sin(x) @ x      # one line, scoped or not
text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
print(seen.count('/jax/compilation_cache/cache_hits'),
      seen.count('/jax/compilation_cache/cache_misses'),
      int('gpt.attn' in text))
'''


def test_jax_cache_never_serves_a_module_compiled_before_its_scope(
        tmp_path):
    """jax's persistent cache leaves metadata out of its key by
    default, so the scoped module would be handed the executable
    compiled without the scope and a profile would never show it.
    setup_xla_cache puts metadata into the key (paths relative to the
    checkout): the scoped module misses, compiles, carries its scope,
    and hits its own entry the next time."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'jc'))
    env.pop('PADDLE_TPU_COMPILE_CACHE', None)

    def child(mode):
        out = subprocess.run([sys.executable, '-c', SCOPE_CHILD, mode],
                             env=env, cwd=str(tmp_path), timeout=300,
                             capture_output=True, text=True)
        assert out.returncode == 0, out.stderr[-2000:]
        return tuple(int(v) for v in out.stdout.split()[-3:])

    hits, misses, named = child('plain')
    assert (hits, named) == (0, 0) and misses
    hits, misses, named = child('scoped')
    assert named == 1 and misses        # not the plain executable
    hits, misses, named = child('scoped')
    assert named == 1 and hits and not misses
