#!/usr/bin/env python
"""Transformer step profiler (BERT/GPT) — the transformer counterpart
of tools/profile_resnet.py.

Measures the EXACT bench.py train step with amortized in-graph chains
where useful: a single dispatch carries host overhead that a kernel's
own time disappears into.

Usage (real chip):
    python tools/profile_transformer.py --model gpt   [--batch 8 --seq 1024]
    python tools/profile_transformer.py --model bert  [--batch 64 --seq 128]

Prints: cost_analysis flops/bytes, measured ms/step (best of 3),
TFLOPS-equivalent (6*N*tokens/s), and the top optimized-HLO op census
(via the shared ``profiler.op_summary`` / ``analysis.hlo`` parser —
the ad-hoc Counter census this script used to carry is gone).

``--emit-telemetry`` additionally captures an on-device trace window
around one timing rep through the shared capture/parse API
(``telemetry.capture``), leaving telemetry JSONL + a
``profile_capture`` breakdown (and census-matched
``collective_observed`` events on multi-device runs) in ``--out`` for
tools/run_report.py / tools/calibrate_costmodel.py.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build(model_name, batch, seq):
    import paddle_tpu as paddle
    from paddle_tpu.parallel import ParallelTrainer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import env as dist_env

    dist_env.set_mesh(None)
    paddle.seed(0)
    if model_name == 'gpt':
        from paddle_tpu.models.gpt import gpt_small
        model = gpt_small(max_seq_len=seq, dropout=0.0)
        n_params = 124e6
    else:
        from paddle_tpu.models.bert import bert_base
        model = bert_base(max_seq_len=seq, dropout=0.0)
        n_params = 110e6
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    st = fleet.DistributedStrategy()
    st.amp = True
    st.amp_configs['use_pure_fp16'] = True
    tr = ParallelTrainer(model, opt, lambda o, y: model.loss(o, y),
                         strategy=st)
    rs = np.random.RandomState(0)
    V = model.config.vocab_size
    ids = rs.randint(0, V, size=(batch, seq)).astype('int64')
    if model_name == 'gpt':
        lbl = ids
    else:
        lbl = np.where(rs.rand(batch, seq) < 0.15,
                       rs.randint(0, V, size=(batch, seq)), -100) \
            .astype('int64')
    return tr, ids, lbl, n_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--model', choices=('gpt', 'bert'), default='gpt')
    ap.add_argument('--batch', type=int, default=None)
    ap.add_argument('--seq', type=int, default=None)
    ap.add_argument('--iters', type=int, default=15)
    ap.add_argument('--emit-telemetry', action='store_true',
                    help='capture a trace window around one timing '
                         'rep and stream telemetry JSONL to --out')
    ap.add_argument('--out', default=None,
                    help='telemetry/trace output dir for '
                         '--emit-telemetry (default: '
                         'chiprun_out/profile_<model>)')
    args = ap.parse_args()
    batch = args.batch or (8 if args.model == 'gpt' else 64)
    seq = args.seq or (1024 if args.model == 'gpt' else 128)
    out = args.out or os.path.join('chiprun_out',
                                   f'profile_{args.model}')

    import jax
    from paddle_tpu.core.compile_cache import setup_xla_cache
    setup_xla_cache()
    from paddle_tpu import telemetry
    print(f'device: {jax.devices()[0]}', flush=True)
    if args.emit_telemetry:
        telemetry.enable(out)
    tr, ids, lbl, n_params = build(args.model, batch, seq)
    # device-resident inputs, exactly like bench.py: measure compute,
    # not the host link
    ids = jax.device_put(ids)
    lbl = jax.device_put(lbl)

    t0 = time.time()
    loss = None
    for _ in range(3):
        loss = tr.step(ids, lbl)
    float(np.asarray(loss))
    print(f'warmup (3 steps incl. compile): {time.time() - t0:.0f}s '
          f'loss={float(np.asarray(loss)):.4f}', flush=True)

    best = None
    for _ in range(3):
        t0 = time.time()
        for _ in range(args.iters):
            loss = tr.step(ids, lbl)
        float(np.asarray(loss))
        dt = (time.time() - t0) / args.iters
        best = dt if best is None or dt < best else best

    if args.emit_telemetry:
        # a SEPARATE short traced window AFTER the headline reps: the
        # window close pays block_until_ready + trace parse + the
        # compiled_text lowering — none of which may touch the
        # best-of-3 measurement (PERF.md methodology)
        n_trace = min(args.iters, 4)
        with telemetry.capture(
                os.path.join(out, 'trace'), name=args.model,
                hlo_text_fn=tr.compiled_text,
                mesh_shape=(dict(tr.mesh.shape)
                            if tr.mesh is not None else None),
                steps=n_trace) as cap:
            for _ in range(n_trace):
                loss = tr.step(ids, lbl)
            cap.sync = loss
        win = cap.windows[-1] if cap.windows else {}
        print(f'trace window ({n_trace} steps): '
              f'{win.get("device_us_per_step", 0):.0f} us/step '
              f'device, '
              f'{win.get("collective_us_per_step", 0):.0f} us '
              f'collectives ({len(cap.observed)} '
              'collective_observed)', flush=True)
    toks = batch * seq / best
    print(f'{args.model} b={batch} T={seq}: {best * 1000:.1f} ms/step '
          f'{toks:.0f} tokens/s '
          f'(~{6 * n_params * toks / 1e12:.1f} TFLOPS-eq, '
          f'{6 * n_params * toks / 1e12 / 197 * 100:.0f}% of v5e peak)',
          flush=True)

    # census LAST: compiled_text() lowers through the AOT path (it
    # does not reuse jit's in-memory executable), so running it after
    # the timing loop keeps the chip idle while measuring (PERF.md
    # methodology rule 2).  One shared lowering serves the module
    # cost totals AND the per-op table (profiler.op_summary over the
    # analysis.hlo parser — and nothing at all when the persistent
    # compile cache already holds this step's text).
    try:
        tr.op_summary(ids, lbl, top=12)
    except Exception as e:
        print(f'op census unavailable: {e!r}', flush=True)
    if args.emit_telemetry:
        telemetry.disable()
        print(f'telemetry JSONL + trace artifacts: {out}', flush=True)


if __name__ == '__main__':
    main()
