"""Runner `train`: ParallelTrainer.step in a loop, one batch a step,
each step ended by block_until_ready on its loss.  The loss is computed
inside the model's forward (`with_loss`), which no entry point of the
repo does today: it is the benchmark's way round the loss closure that
bakes the head's weight into the step (PERF.md, PR 24, finding 1), so
the rate is that of the step a repaired caller would get, not of
bench.py's or chip_smoke.py's.

correct = the probe (the trainer's first loss against the float32
reference on the same parameters and batch, and the warm-up losses on
that batch falling), taken before the window and the profiler, and no
non-finite loss in the window.  Nothing read from the trace or the
clock enters it.
"""
import importlib
import os
import statistics
import time

import numpy as np

TRACED_STEPS = 4


PREFIX = 'lm.'


def with_loss(lm):
    """The model as a Layer whose forward returns the LM loss.

    bench.py and chip_smoke.py hand ParallelTrainer
    `lambda out, y: model.loss(out, y)`.  With the fused head that
    closure reads the tied embedding from the live Layer after
    functional_call has put the eager weights back, so the [V, H]
    matrix enters the compiled step as a constant made from the seed:
    the head's weight is never trained through the loss, every seed is
    another program, and jax's persistent cache cannot serve it (PR 24,
    PERF.md).  Computing the loss inside forward keeps every weight an
    argument of the step.
    """
    from paddle_tpu import nn

    class LMWithLoss(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lm = lm

        def forward(self, ids, labels):
            return self.lm.loss(self.lm(ids), labels)

    return LMWithLoss()


def build(config, seed, mesh_axes=None):
    """The model and trainer, with bench.py's gpt settings; `mesh` in
    the configuration puts them on a device mesh."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.parallel import ParallelTrainer

    mesh = dist_env.build_mesh(dict(mesh_axes)) if mesh_axes else None
    dist_env.set_mesh(mesh)
    paddle.seed(seed)
    model_cfg = {k: v for k, v in config['model'].items()
                 if k != 'published_vocab_size'}
    model = GPTForCausalLM(GPTConfig(**model_cfg))
    tr = config['trainer']
    if tr['optimizer'] != 'AdamW' or tr['amp'] != 'O2':
        raise ValueError(f'runner train knows AdamW under AMP O2, not '
                         f'{tr}')
    opt = paddle.optimizer.AdamW(learning_rate=tr['learning_rate'],
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs['use_pure_fp16'] = True
    trainer = ParallelTrainer(with_loss(model), opt, lambda loss: loss,
                              strategy=strategy, mesh=mesh, n_inputs=2)
    return model, trainer


def probe(config, trainer, ids, say, compared, perturb=0.0):
    """Reference loss on the trainer's parameters as they stand, then
    the warm-up steps on the same batch.  Returns (ok, detail)."""
    import jax
    from benchmark.reference import gpt_ref
    m, p = config['model'], config['probe']
    params = {k[len(PREFIX):]: v + perturb if perturb else v
              for k, v in trainer.params.items()}
    t0 = time.monotonic()
    want = gpt_ref.lm_loss(params, ids, num_layers=m['num_layers'],
                           num_heads=m['num_heads'],
                           eps=m.get('layer_norm_epsilon', 1e-5))
    t1 = time.monotonic()
    losses = [float(np.asarray(jax.block_until_ready(
        trainer.step(ids, ids)))) for _ in range(p['warmup_steps'])]
    t2 = time.monotonic()
    rel = abs(losses[0] - want) / abs(want)
    falling = all(np.isfinite(losses)) and losses[-1] < losses[0]
    say(f'probe: first loss {losses[0]:.6f} reference {want:.6f} '
        f'rel {rel:.2e} (tol {p["loss_rel_tol"]:.0e}); warm-up losses '
        f'{[round(v, 4) for v in losses]}; reference {t1 - t0:.1f}s, '
        f'{p["warmup_steps"]} warm-up steps {t2 - t1:.1f}s')
    compared['first_loss_rel'] = [float(np.nan_to_num(rel, nan=np.inf)),
                                  float(p['loss_rel_tol'])]
    compared['warmup_loss_not_falling'] = [0 if falling else 1, 0]
    return rel <= p['loss_rel_tol'] and falling


def run(cell, seed, seconds, trace_on, t_start, say,
        reference_perturb=0.0):
    import jax
    from benchmark import harness
    config, traffic = cell['config'], cell['traffic']
    compiles = harness.CompileCounter()
    t0 = time.monotonic()
    _model, trainer = build(config, seed, config.get('mesh'))
    batches = importlib.import_module(
        'benchmark.generators.' + traffic['generator']).make(traffic, seed)
    t1 = time.monotonic()
    say(f'model and trainer {t1 - t0:.1f}s')
    compared = {}
    probe_ok = probe(config, trainer, batches.batch(0), say, compared,
                     perturb=reference_perturb)
    say(f'set-up compile cache: {compiles.hits} hits, {compiles.misses} '
        f'misses of {compiles.built} programs')
    compiled_before = compiles.built

    tokens_per_step = int(np.prod(batches.shape))
    tracer = harness.TraceWindow(cell['name']) if trace_on else None
    traced_left = 0
    step_s, parts, losses = [], [], []
    t_window = time.monotonic()
    setup_s = t_window - t_start
    cpu_before = time.process_time()
    t_prev = t_window
    step = 0
    while t_prev - t_window < seconds:
        step += 1
        if tracer is not None and not tracer.done and not tracer.open \
                and t_prev - t_window >= seconds / 2:
            tracer.start()
            traced_left = TRACED_STEPS
            t_prev = time.monotonic()
        with jax.profiler.TraceAnnotation('bench.data'):
            ids = batches.batch(step)
        t_data = time.monotonic()
        with jax.profiler.TraceAnnotation('bench.train_step'):
            loss = trainer.step(ids, ids)
        t_sent = time.monotonic()
        with jax.profiler.TraceAnnotation('bench.wait_step'):
            losses.append(float(np.asarray(
                jax.block_until_ready(loss))))
        now = time.monotonic()
        step_s.append(now - t_prev)
        parts.append((t_data - t_prev, t_sent - t_data, now - t_sent))
        t_prev = now
        if traced_left:
            traced_left -= 1
            if not traced_left:
                tracer.stop()
                t_prev = time.monotonic()
    # the window closes with the step that was running when --seconds
    # had passed, so the rate is over whole steps and all their time
    window_s = t_prev - t_window - (tracer.stall_s if tracer else 0.0)
    failed = sum(1 for v in losses if not np.isfinite(v))
    rate = len(losses) * tokens_per_step / window_s
    say(f'window: {len(losses)} steps in {window_s:.3f}s, last loss '
        f'{losses[-1]:.4f}, {compiles.built - compiled_before} '
        'compiles in the window')
    # where a low rate came from: the steps far over the median, each
    # split into making the batch, dispatching the step and waiting
    typical = statistics.median(step_s)
    slow = [i for i, v in enumerate(step_s) if v > 1.25 * typical]
    say(f'steps: median {typical * 1e3:.1f} ms, max '
        f'{max(step_s) * 1e3:.1f}; {len(slow)} over 1.25x the median, '
        f'{sum(step_s[i] - typical for i in slow):.3f}s lost in them; '
        f'process CPU {time.process_time() - cpu_before:.1f}s, load '
        f'{os.getloadavg()[0]:.2f}; step index, ms [data, dispatch, '
        'wait]: ' + '; '.join(
            f'{i} {step_s[i] * 1e3:.0f} '
            f'{[round(v * 1e3, 1) for v in parts[i]]}'
            for i in slow[:8]))
    return {
        'correct': probe_ok and failed == 0,
        'compared': dict(compared, non_finite_losses=[failed, 0]),
        'attempted': len(losses), 'failed': failed,
        'end_to_end': {'train_tokens_per_s': (rate, 'tokens/s'),
                       'setup_s': (setup_s, 's')},
        'counters': {
            'step_ms_median': statistics.median(step_s) * 1e3,
            'tokens_per_s': rate,
            'tokens_per_step': tokens_per_step,
            'traced_steps': TRACED_STEPS,
            'compiles_in_window': compiles.built - compiled_before,
            'peak_hbm_bytes': harness.device_info()['memory_peak_bytes'],
        },
        'trace': tracer.load() if tracer else None,
    }
