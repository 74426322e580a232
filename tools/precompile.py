#!/usr/bin/env python
"""precompile — AOT warm start: compile the declared bucket set at
export time so a restarted (or freshly served) worker deserializes
instead of recompiling.

    python tools/precompile.py RUN_DIR                      # defaults
    python tools/precompile.py RUN_DIR --targets lenet,gpt --mesh dp=4
    python tools/precompile.py RUN_DIR \\
        --gpt-decode 8x128x128,8x64x128 --gpt-model small
    python tools/precompile.py RUN_DIR --json

What gets compiled (all without ever executing a step):

* **train-step lowerings** — the built-in audit targets
  (analysis.targets: gpt / widedeep / lenet) lowered through the SPMD
  partitioner for every requested mesh, landing in the persistent
  compile cache's TEXT tier (the exact keys ``tpu_lint --plan``/
  ``--hlo`` and the planner read) and seeding jax's persistent XLA
  cache with the compiled executables;
* **gptgen decode buckets** — ``--gpt-decode BxT0xNEW`` signatures
  exported through ``GPTForCausalLM.precompile_decode`` into the EXEC
  tier (serialized ``jax.export`` artifacts, prompt lengths bucketed
  to the next power of two) plus an AOT XLA compile, so a serving
  cold-start's ``generate`` deserializes and skips the optimizer
  passes too;
* **elastic-reshape target meshes** — when RUN_DIR holds committed
  sharded checkpoints, the newest step's commit manifest records the
  saving mesh (PR 5's reshape metadata); its dp axis halved (dp/2,
  dp/4, ...) is added to the mesh set, so the reshape-restore path a
  preempted pool takes onto fewer hosts finds its lowerings warm.

Every produced entry is recorded in a sidecar
``_PADDLE_PRECOMPILE.json`` committed into RUN_DIR:
``check_ckpt --deep`` audits it (a restore target's AOT set is
provable), and ``warm_start`` (called by auto_checkpoint /
CheckpointManager.restore) pre-loads it on the next restart.

Exit codes: 0 = every requested artifact compiled, 1 = some failed
(the manifest still records the ones that succeeded), 2 = usage error.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _parse_mesh(spec):
    axes = {}
    for part in spec.split(','):
        name, _, size = part.strip().partition('=')
        if not size:
            raise ValueError(f'--mesh wants axis=size, got {part!r}')
        axes[name] = int(size)
    return axes


def _parse_decode(spec):
    """'8x128x128,2x16x8' -> [(B, T0, NEW), ...]."""
    out = []
    for part in spec.split(','):
        dims = part.strip().lower().split('x')
        if len(dims) != 3:
            raise ValueError(
                f'--gpt-decode wants BxT0xNEW, got {part!r}')
        out.append(tuple(int(d) for d in dims))
    return out


def _reshape_meshes(run_dir):
    """Elastic-reshape targets from the newest committed step's
    manifest: the saved mesh itself plus its dp axis halved down to 1
    — the meshes a preempted pool restores onto."""
    from paddle_tpu.resilience import manifest as M
    steps = []
    try:
        for f in os.listdir(run_dir):
            tag = f.rpartition('_')[2]
            if tag.isdigit() and os.path.isdir(os.path.join(run_dir, f)):
                steps.append((int(tag), os.path.join(run_dir, f)))
    except OSError:
        return []
    for _s, p in sorted(steps, reverse=True):
        doc = M.read_manifest(p)
        if doc is None or not doc.get('mesh'):
            continue
        mesh = {a: int(s) for a, s in doc['mesh'].items()}
        out = [dict(mesh)]
        dp = mesh.get('dp', 1)
        while dp > 1:
            dp //= 2
            # dp=1 included: a pool shrinking to a single host is the
            # most-shrunk elastic target and still wants a warm lower
            out.append(dict(mesh, dp=dp))
        return out
    return []


def _build_mesh(axes):
    import math
    import numpy as np
    import jax
    from jax.sharding import Mesh
    n = math.prod(axes.values())
    devs = jax.devices()
    if n > len(devs):
        raise RuntimeError(
            f'mesh {axes} wants {n} devices but only {len(devs)} exist')
    return Mesh(np.array(devs[:n]).reshape(tuple(axes.values())),
                tuple(axes.keys()))


def _precompile_target(name, mesh_axes, entries, errors,
                       fused_steps=0):
    """Lower one audit target's surrogate step for one mesh into the
    persistent text tier (exact tpu_lint/planner keys) — the
    lower+compile also seeds jax's XLA disk cache.  ``fused_steps=K``
    instead lowers the K-step FUSED module (core.scan_loop: one
    lax.scan over a K-stacked batch) under a distinct cache key, so a
    deploy that trains with ``fused_steps=K`` finds its whole-loop
    module warm."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.analysis import hlo as _hlo
    from paddle_tpu.analysis import targets as _targets
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.core import scan_loop as _scan
    from paddle_tpu.distributed import env as _env
    k = max(0, int(fused_steps))
    desc = f'target-step {name} @ {mesh_axes or "1-device"}' + \
        (f' fused x{k}' if k else '')
    try:
        mesh = _build_mesh(mesh_axes) if mesh_axes else \
            _build_mesh({'dp': 1})
        prev = _env.get_mesh()
        _env.set_mesh(mesh)
        try:
            model, batch = _targets.TARGETS[name](mesh)
            params, buffers, p_sh, b_sh = _targets.target_state(
                model, mesh)
            repl = NamedSharding(mesh, P())
            batch_sh = _targets.batch_shardings(mesh, batch)
            key = jax.random.PRNGKey(0)
            step = _targets.surrogate_step(model)
            ck_name = name if not k else f'{name}+fused{k}'
            if k:
                # stack the batch with a leading K dim and shift the
                # dp sharding one dim right — the fused scan's axes
                step = _scan.fused_surrogate(step, k)
                batch = tuple(jax.ShapeDtypeStruct((k,) + tuple(b.shape),
                                                   b.dtype)
                              for b in batch)
                batch_sh = tuple(
                    NamedSharding(mesh, P(None, *sh.spec))
                    for sh in batch_sh)
            ck = _targets.cache_key(ck_name, mesh.shape, p_sh, batch_sh,
                                    batch=batch)
            _hlo.lower_text(
                step, params, buffers, key, *batch,
                jit_kwargs={'in_shardings': (p_sh, b_sh, repl)
                            + batch_sh},
                lower_cache={}, cache_key=ck)
        finally:
            _env.set_mesh(prev)
        fp = _cc.fingerprint('lower-text', key=ck)
        if fp is not None and _cc.get('hlo', fp) is not None:
            entries.append({'tier': 'hlo', 'fingerprint': fp,
                            'description': desc})
        else:
            errors[desc] = 'entry not committed (cache disabled?)'
    except Exception as e:
        errors[desc] = repr(e)


def _precompile_serve(config_path, entries, errors):
    """--serve CONFIG: AOT-compile the WHOLE serving surface a config
    declares — every prompt-bucket prefill module and every
    (batch bucket x decode span) fused decode module
    (paddle_tpu/serving) — into the exec tier, so a serving cold
    start deserializes instead of tracing (zero cold-start compiles).
    Returns the engine's declared bucket set for the sidecar meta."""
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt as _gpt
    from paddle_tpu.serving import ServeConfig, ServingEngine
    try:
        with open(config_path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        errors[f'serve config {config_path}'] = repr(e)
        return None
    model_name = doc.get('model', 'small')
    builders = {'tiny': _gpt.gpt_tiny, 'small': _gpt.gpt_small}
    if model_name not in builders:
        errors[f'serve config {config_path}'] = \
            f'unknown model {model_name!r} (have {list(builders)})'
        return None
    paddle.seed(0)
    kw = dict(doc.get('model_kwargs') or {})
    kw.setdefault('dropout', 0.0)
    try:
        model = builders[model_name](**kw)
        engine = ServingEngine(model, ServeConfig.from_json(doc))
        serve_entries, serve_errors = engine.precompile()
    except Exception as e:
        errors[f'serve config {config_path}'] = repr(e)
        return None
    entries.extend(serve_entries)
    errors.update({f'serve: {k}': v for k, v in serve_errors.items()})
    return dict(engine.bucket_set(), model=model_name)


def _precompile_decode(model_name, shape, kwargs, entries, errors):
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt as _gpt
    B, T0, new = shape
    desc = f'gpt-decode {model_name} b{B} p{T0} n{new}'
    try:
        paddle.seed(0)
        builders = {'tiny': _gpt.gpt_tiny, 'small': _gpt.gpt_small}
        default_len = 128 if model_name == 'tiny' else 1024
        model = builders[model_name](
            max_seq_len=max(default_len, T0 + new), dropout=0.0)
        model.eval()
        fp, P = model.precompile_decode(B, T0, new, **kwargs)
        if fp is None:
            errors[desc] = 'no fingerprint (cache disabled?)'
            return
        from paddle_tpu.core import compile_cache as _cc
        if _cc.get('exec', fp) is None:
            # the export itself failed (non-exportable trace, torn
            # write, disk full) — recording the entry anyway would
            # make check_ckpt --deep fail LATER with no error at the
            # moment the operator could act
            errors[desc] = 'entry not committed (export failed?)'
            return
        entries.append({'tier': 'exec', 'fingerprint': fp,
                        'description': f'{desc} (bucket {P})'})
    except Exception as e:
        errors[desc] = repr(e)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='precompile',
        description='AOT-compile the declared bucket set into the '
                    'persistent compile cache and commit a sidecar '
                    'manifest next to a checkpoint run dir.')
    ap.add_argument('run_dir',
                    help='checkpoint run directory the sidecar '
                         'manifest is committed into (created if '
                         'absent)')
    ap.add_argument('--targets', default='gpt,widedeep,lenet',
                    help='comma-separated built-in train-step targets '
                         '(gpt,widedeep,lenet); "none" to skip')
    ap.add_argument('--mesh', metavar='SPEC', default=None,
                    help='mesh axes to lower the targets for, e.g. '
                         '"dp=4" or "dp=2,tp=2" (default: single '
                         'device, plus any reshape meshes recorded in '
                         'the run dir\'s newest commit manifest)')
    ap.add_argument('--fused-steps', metavar='K[,K2,...]', default=None,
                    help='additionally AOT-lower each target\'s '
                         'K-step FUSED train module (core.scan_loop '
                         'whole-loop compilation) for these chunk '
                         'lengths, e.g. "8,32" — a deploy training '
                         'with fused_steps=K then warm-starts its '
                         'fused module too')
    ap.add_argument('--gpt-decode', metavar='BxT0xNEW[,...]',
                    default=None,
                    help='gptgen decode bucket signatures to export, '
                         'e.g. "8x128x128,8x64x128" (prompt lengths '
                         'are bucketed to the next power of two)')
    ap.add_argument('--serve', metavar='CONFIG', default=None,
                    help='serving config JSON (paddle_tpu/serving '
                         'ServeConfig fields + "model"/"model_kwargs")'
                         ': AOT-compile its WHOLE declared bucket set '
                         '— every prompt-bucket prefill and every '
                         'batch-bucket fused decode module — so a '
                         'serving cold start deserializes instead of '
                         'tracing')
    ap.add_argument('--gpt-model', choices=('tiny', 'small'),
                    default='small',
                    help='GPT config the decode buckets compile for')
    ap.add_argument('--temperature', type=float, default=0.0,
                    help='decode sampling temperature baked into the '
                         'exported modules (default 0 = greedy)')
    ap.add_argument('--top-k', type=int, default=None,
                    help='decode top-k baked into the exported modules')
    ap.add_argument('--cache', metavar='DIR', default=None,
                    help='compile-cache directory (sets '
                         'PADDLE_TPU_COMPILE_CACHE for this run)')
    ap.add_argument('--json', action='store_true',
                    help='machine-readable summary on stdout')
    args = ap.parse_args(argv)

    if args.cache:
        os.environ['PADDLE_TPU_COMPILE_CACHE'] = args.cache
    try:
        mesh_axes = _parse_mesh(args.mesh) if args.mesh else None
        decode = _parse_decode(args.gpt_decode) if args.gpt_decode \
            else []
    except ValueError as e:
        print(f'precompile: {e}', file=sys.stderr)
        return 2

    from paddle_tpu.core import compile_cache as _cc
    if not _cc.enabled():
        print('precompile: the exec/text tiers are off; name a '
              f'directory with --cache or {_cc.ENV_VAR}',
              file=sys.stderr)
        return 2

    target_names = [] if args.targets.strip().lower() == 'none' else \
        [t.strip() for t in args.targets.split(',') if t.strip()]
    meshes = [mesh_axes] if mesh_axes else [None]
    reshape = _reshape_meshes(args.run_dir)
    for m in reshape:
        if m not in meshes:
            meshes.append(m)

    try:
        fused = [int(x) for x in args.fused_steps.split(',')
                 if x.strip()] if args.fused_steps else []
        if any(x < 1 for x in fused):
            raise ValueError('--fused-steps wants K >= 1')
    except ValueError as e:
        print(f'precompile: {e}', file=sys.stderr)
        return 2

    entries, errors = [], {}
    for m in meshes:
        for name in target_names:
            _precompile_target(name, m, entries, errors)
            for k in fused:
                _precompile_target(name, m, entries, errors,
                                   fused_steps=k)
    kwargs = {'temperature': args.temperature, 'top_k': args.top_k}
    for shape in decode:
        _precompile_decode(args.gpt_model, shape, kwargs, entries,
                           errors)
    serve_buckets = None
    if args.serve:
        serve_buckets = _precompile_serve(args.serve, entries, errors)

    doc = _cc.write_precompile_manifest(
        args.run_dir, entries,
        meta={'meshes': [m or {} for m in meshes],
              'reshape_meshes': reshape,
              'fused_steps': fused,
              'serve_buckets': serve_buckets})
    summary = {'run_dir': os.path.abspath(args.run_dir),
               'cache_dir': _cc.cache_dir(),
               'entries': len(entries),
               'errors': errors,
               'meshes': doc['meshes']}
    if args.json:
        print(json.dumps(summary, indent=1, sort_keys=True))
    else:
        print(f'precompiled {len(entries)} artifact(s) into '
              f'{_cc.cache_dir()}')
        for e in entries:
            print(f'  {e["tier"]:<5} {e["fingerprint"][:16]}  '
                  f'{e["description"]}')
        for desc, err in errors.items():
            print(f'  FAILED {desc}: {err}')
        print(f'sidecar manifest: '
              f'{os.path.join(os.path.abspath(args.run_dir), _cc.PRECOMPILE_MANIFEST)}')
    return 1 if errors else 0


if __name__ == '__main__':
    sys.exit(main())
