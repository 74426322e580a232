#!/usr/bin/env python
"""tpu_lint — sweep Python sources for TPU compilation hazards.

The CLI front of paddle_tpu.analysis: AST-lints files/directories (no
imports, no device, no execution — safe on any tree), and optionally
deep-lints one callable's jaxpr.

    python tools/tpu_lint.py examples/ paddle_tpu/models/
    python tools/tpu_lint.py train.py --scope all       # audit host loops
    python tools/tpu_lint.py examples/ --json           # machine output
    python tools/tpu_lint.py x.py --disable host-sync
    python tools/tpu_lint.py --jaxpr pkg.mod:fn --shapes 8x128xf32,8xi32
    python tools/tpu_lint.py examples/ --hlo --mesh dp=8   # SPMD audit
    python tools/tpu_lint.py --plan --chips 8 [--hbm-gb 16]  # planner
    python tools/tpu_lint.py paddle_tpu/ --threads    # concurrency lint
    python tools/tpu_lint.py paddle_tpu/ --spmd     # SPMD contract lint

--threads swaps the sweep for the concurrency rules
(paddle_tpu.analysis.threads): guarded-by (annotated shared state
accessed outside its lock), blocking-under-lock (device syncs /
network / file IO / sleeps inside a critical section), and
daemon-thread-lifecycle (daemon threads with no stop/join path).
Pure source analysis, same suppression grammar; the tier-1 gate
(tests/test_analysis_threads.py) runs it over paddle_tpu/ at zero
HIGH.

--spmd swaps the sweep for the SPMD-contract rules
(paddle_tpu.analysis.spmd): rank-dependent-collective (a collective
reachable on only one side of a rank/process_index/env guard — the
deadlock hazard), collective-order (branch paths must issue identical
collective sequences; the HLO half joins hlo.collective_instrs
through `conditional`s on every --hlo audit), host-nondeterminism-
into-trace (time/env/host-random feeding traced values or collective
payloads without a broadcast) and unbroadcast-rng (host-local entropy
seeding per-rank keys).  Same suppression grammar; the tier-1 gate
(tests/test_analysis_spmd.py) runs it over paddle_tpu/ + tools/ at
zero HIGH.

--hlo escalates to the lowered-HLO SPMD audit (paddle_tpu.analysis.hlo):
each target step is lowered through jax.jit under a FORCED virtual
mesh (--mesh dp=8 / dp=4,tp=2 — CPU devices, no chip touched, no
execution), the compiled post-partitioner module is parsed, and the
HLO rules run: replicated-giant-hlo, collective-cost (ring
byte/latency estimates per all-reduce/all-gather/reduce-scatter/
all-to-all/collective-permute), resharding, peak-memory (liveness
high-water vs --hbm-gb).  For examples/ + paddle_tpu/models/ paths a
built-in suite of representative tiny step functions (GPT dp+tp,
WideDeep, LeNet — the models the examples train) is lowered; --jaxpr
targets are HLO-audited directly.

--plan runs the auto-sharding planner (paddle_tpu.analysis.planner)
over the same built-in suite: every dp/tp/pp factorization of --chips
(2D/3D torus layouts included) crossed with PartitionSpec assignments
(declared tp specs / fully replicated / fsdp dim-0) is lowered through
the partitioner and ranked by predicted step cost (torus-decomposed
collective wire time + a per-device compute floor) under the --hbm-gb
budget, with remat / half-batch fallback plans when nothing fits.
--plan and --hlo share one lowering per (target, mesh, shardings)
triple.  --calibration swaps measured alpha/beta (from
tools/calibrate_costmodel.py) into the cost model.

Exit codes: 0 = no findings at/above --fail-on (default: high),
1 = findings at/above --fail-on, 2 = usage error, or an --hlo
infra failure (mesh build / lower crashed: the text/JSON report is
still printed, with the error under "hlo_error").  CI scripts
consume --json; the tier-1 self-lint gates
(tests/test_analysis.py, tests/test_analysis_hlo.py) run this over
examples/ and paddle_tpu/models/ (AST and --hlo) and require exit 0.

Suppress a finding with `# tpu-lint: disable=<rule-id>` on its line.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_SEVS = ('info', 'warn', 'high')


_DTYPE_TOKENS = {
    'f16': 'float16', 'f32': 'float32', 'f64': 'float64',
    'i8': 'int8', 'i16': 'int16', 'i32': 'int32', 'i64': 'int64',
    'u8': 'uint8', 'u32': 'uint32', 'bool': 'bool',
}


def _parse_shapes(spec):
    """'8x128xf32,8xi32' -> [ShapeDtypeStruct] (last token = dtype;
    short tokens f32/i32/bf16/... or any numpy dtype name)."""
    import numpy as np
    import jax.numpy as jnp
    import jax
    out = []
    for part in spec.split(','):
        toks = part.strip().split('x')
        tok = toks[-1]
        if tok == 'bf16':
            dtype = jnp.bfloat16
        else:
            dtype = np.dtype(_DTYPE_TOKENS.get(tok, tok))
        shape = tuple(int(t) for t in toks[:-1])
        out.append(jax.ShapeDtypeStruct(shape, dtype))
    return out


def _resolve(target):
    import importlib
    mod_name, _, fn_name = target.partition(':')
    if not fn_name:
        raise SystemExit(f'--jaxpr needs module:function, got {target!r}')
    mod = importlib.import_module(mod_name)
    return getattr(mod, fn_name)


# -- the lowered-HLO SPMD audit (--hlo) ---------------------------------------

def _parse_mesh(spec):
    """'dp=8' / 'dp=4,tp=2' -> ordered {axis: size}."""
    axes = {}
    for part in spec.split(','):
        name, _, size = part.strip().partition('=')
        if not size:
            raise ValueError(f'--mesh wants axis=size, got {part!r}')
        axes[name] = int(size)
    return axes


def _force_mesh_env(axes, min_devices=0):
    """Make enough virtual devices exist BEFORE jax imports.  The
    audit never executes device code, so CPU host devices are exactly
    as good as chips for lowering through the SPMD partitioner.
    Without --mesh the default is dp=8: forcing 1 device would make
    every SPMD rule silently vacuous.  ``min_devices`` raises the
    floor (--plan --chips N wants N devices regardless of --mesh)."""
    n = 1
    for v in (axes or {'dp': 8}).values():
        n *= v
    n = max(n, int(min_devices))
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + f' --xla_force_host_platform_device_count={n}'
        ).strip()


def _build_mesh(axes):
    import numpy as np
    import jax
    from jax.sharding import Mesh
    if not axes:
        axes = {'dp': len(jax.devices())}
    n = 1
    for v in axes.values():
        n *= v
    devs = jax.devices()
    if n > len(devs):
        raise SystemExit(
            f'tpu_lint: mesh {axes} wants {n} devices but only '
            f'{len(devs)} exist (is JAX_PLATFORMS set to a fixed '
            'backend before the forced device count could apply?)')
    return Mesh(np.array(devs[:n]).reshape(tuple(axes.values())),
                tuple(axes.keys()))


def _run_hlo_suite(mesh, target_names, thresholds, disable,
                   lower_cache=None):
    """Lower + audit each built-in target (analysis.targets);
    returns {name: LintReport}.  `lower_cache` is the shared memo —
    when ``--plan`` already lowered this exact (target, mesh,
    shardings) triple, the audit reuses that compiled text instead of
    paying trace+lower a second time."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu import analysis
    from paddle_tpu.analysis import targets as _targets
    from paddle_tpu.distributed import env as _env
    reports, errors = {}, {}
    prev_mesh = _env.get_mesh()
    _env.set_mesh(mesh)     # model-internal maybe_shard constraints live
    try:
        for name in target_names:
            # per-target isolation: one broken lower must not discard
            # the audits of the targets that DO lower
            try:
                model, batch = _targets.TARGETS[name](mesh)
                params, buffers, p_sh, b_sh = _targets.target_state(
                    model, mesh)
                repl = NamedSharding(mesh, P())
                batch_sh = _targets.batch_shardings(mesh, batch)
                key = jax.random.PRNGKey(0)
                ck = _targets.cache_key(name, mesh.shape, p_sh,
                                        batch_sh, batch=batch)
                reports[name] = analysis.lint_hlo(
                    _targets.surrogate_step(model), params, buffers,
                    key, *batch, mesh=mesh,
                    in_shardings=(p_sh, b_sh, repl) + batch_sh,
                    thresholds=thresholds, disable=disable,
                    lower_cache=lower_cache, cache_key=ck,
                    name=f'hlo:{name}')
            except Exception as e:
                errors[name] = repr(e)
                print(f'tpu_lint: --hlo target {name} failed: {e!r}',
                      file=sys.stderr)
    finally:
        _env.set_mesh(prev_mesh)
    return reports, errors


def _run_plan_suite(target_names, chips, *, hbm_gb=None,
                    calibration=None, include_pp=True,
                    max_candidates=None, lower_cache=None):
    """Auto-sharding planner over the built-in targets; returns
    ({name: PlanResult}, {name: error})."""
    from paddle_tpu.analysis import planner
    results, errors = {}, {}
    for name in target_names:
        try:
            results[name] = planner.plan_target(
                name, chips=chips, hbm_budget_gb=hbm_gb,
                calibration=calibration, include_pp=include_pp,
                max_candidates=max_candidates,
                lower_cache=lower_cache)
        except Exception as e:
            errors[name] = repr(e)
            print(f'tpu_lint: --plan target {name} failed: {e!r}',
                  file=sys.stderr)
    return results, errors


def _render_hlo_extras(extras, out=sys.stdout):
    mesh = extras.get('mesh')
    print(f'    mesh={mesh} partitions={extras.get("n_partitions")}',
          file=out)
    census = extras.get('collectives') or {}
    if not census:
        print('    collectives: none', file=out)
    for op, row in sorted(census.items()):
        print(f'    {op}: {row["calls"]} calls, '
              f'{row["bytes"] / (1 << 20):.2f} MiB buffers, '
              f'{row["wire_bytes"] / (1 << 20):.2f} MiB wire, '
              f'~{row["est_us"]:.0f} us (ring, '
              f'{row["group_size"]} devices)', file=out)
    peak = extras.get('peak_bytes')
    budget = extras.get('hbm_budget_bytes')
    if peak is not None:
        line = f'    peak memory: {peak / (1 << 30):.3f} GiB per device'
        if budget is not None:
            line += f' (budget {budget / (1 << 30):.1f} GiB)'
        print(line, file=out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='tpu_lint',
        description='jaxpr/AST TPU lint: recompile hazards, host '
                    'syncs, sharding & dtype audits.')
    ap.add_argument('paths', nargs='*',
                    help='.py files or directories to AST-lint')
    ap.add_argument('--scope', choices=('traced', 'all'),
                    default='traced',
                    help="'traced' lints only code the framework will "
                         "trace (to_static/jit/forward); 'all' audits "
                         'every function (host step loops)')
    ap.add_argument('--disable', action='append', default=[],
                    metavar='RULE', help='rule id to skip (repeatable)')
    ap.add_argument('--fail-on', choices=_SEVS + ('never',),
                    default='high',
                    help='lowest severity that makes the exit code '
                         'non-zero (default: high)')
    ap.add_argument('--json', action='store_true',
                    help='machine-readable output for CI/bench scripts')
    ap.add_argument('--jaxpr', metavar='MOD:FN',
                    help='additionally deep-lint one callable by '
                         'tracing its jaxpr (imports the module)')
    ap.add_argument('--shapes', metavar='SPEC',
                    help='example shapes for --jaxpr, e.g. '
                         '"8x128xf32,8xi32" (last token is the dtype)')
    ap.add_argument('--fused', type=int, metavar='K', default=None,
                    help='audit the --jaxpr target in its FUSED '
                         'posture (core.scan_loop, fused_steps=K): '
                         'the chunk-break rule flags host '
                         'callbacks/syncs that would force a K-step '
                         'chunk to split back into per-step '
                         'dispatches')
    ap.add_argument('--hlo', action='store_true',
                    help='lowered-HLO SPMD audit: lower step functions '
                         'through the partitioner under a forced mesh '
                         'and run the HLO rules (replicated-giant-hlo, '
                         'collective-cost, resharding, peak-memory). '
                         'Audits the built-in model suite for '
                         'examples//models/ paths and any --jaxpr '
                         'target; no device execution')
    ap.add_argument('--mesh', metavar='SPEC',
                    help='forced mesh axes for --hlo, e.g. "dp=8" or '
                         '"dp=4,tp=2" (virtual CPU devices are created '
                         'as needed; default: all visible devices on '
                         'one dp axis, forcing 8 virtual CPU devices '
                         'when the backend is not already pinned)')
    ap.add_argument('--hbm-gb', type=float, metavar='GiB',
                    help='per-device HBM budget the peak-memory rule '
                         'and the planner gate against (default: 16)')
    ap.add_argument('--plan', action='store_true',
                    help='auto-sharding planner: enumerate candidate '
                         'mesh shapes (dp/tp/pp factorizations of '
                         '--chips) and PartitionSpec assignments for '
                         'the built-in model suite, score each by '
                         'lowering through the partitioner (collective '
                         'wire cost + peak HBM, no execution) and '
                         'print the ranked plans; shares lowerings '
                         'with --hlo')
    ap.add_argument('--chips', type=int, metavar='N',
                    help='device count the planner plans for '
                         '(default: 8 virtual CPU devices)')
    ap.add_argument('--targets', metavar='NAMES',
                    help='comma-separated built-in targets for --plan '
                         '(gpt,widedeep,lenet; default: all)')
    ap.add_argument('--calibration', metavar='FILE',
                    help='measured alpha/beta calibration table '
                         '(tools/calibrate_costmodel.py output) the '
                         'cost model substitutes for its analytic '
                         'defaults')
    ap.add_argument('--max-candidates', type=int, metavar='K',
                    help='cap on lowered plan candidates per target')
    ap.add_argument('--no-pp', action='store_true',
                    help='exclude pipeline (pp>1) layouts from the '
                         'plan enumeration')
    ap.add_argument('--threads', action='store_true',
                    help='concurrency lint instead of the host-sync '
                         'sweep: guarded-by, blocking-under-lock and '
                         'daemon-thread-lifecycle over PATHS (pure '
                         'source analysis, no imports)')
    ap.add_argument('--spmd', action='store_true',
                    help='SPMD contract lint instead of the host-sync '
                         'sweep: rank-dependent-collective, '
                         'collective-order, host-nondeterminism-into-'
                         'trace and unbroadcast-rng over PATHS (pure '
                         'source analysis, no imports)')
    args = ap.parse_args(argv)

    if not args.paths and not args.jaxpr and not args.plan:
        ap.print_usage(sys.stderr)
        print('tpu_lint: nothing to lint (give paths, --jaxpr or '
              '--plan)', file=sys.stderr)
        return 2
    if args.threads and not args.paths:
        ap.print_usage(sys.stderr)
        print('tpu_lint: --threads needs paths to sweep',
              file=sys.stderr)
        return 2
    if args.spmd and not args.paths:
        ap.print_usage(sys.stderr)
        print('tpu_lint: --spmd needs paths to sweep',
              file=sys.stderr)
        return 2
    for p in args.paths:
        if not os.path.exists(p):
            print(f'tpu_lint: no such path: {p}', file=sys.stderr)
            return 2

    mesh_axes = None
    if args.plan and not args.chips:
        args.chips = 8
    if args.hlo or args.plan:
        try:
            mesh_axes = _parse_mesh(args.mesh) if args.mesh else None
        except ValueError as e:
            print(f'tpu_lint: {e}', file=sys.stderr)
            return 2
        # BEFORE the first jax import (analysis pulls jax in)
        _force_mesh_env(mesh_axes, min_devices=args.chips or 0)

    from paddle_tpu import analysis

    report = analysis.LintReport(name='tpu-lint')
    if args.paths:
        if args.threads:
            report.extend(analysis.lint_threads_sources(
                args.paths, disable=args.disable))
        elif args.spmd:
            report.extend(analysis.lint_spmd_sources(
                args.paths, disable=args.disable))
        else:
            report.extend(analysis.lint_sources(
                args.paths, scope=args.scope, disable=args.disable))
    if args.jaxpr:
        try:
            fn = _resolve(args.jaxpr)
        except (ImportError, AttributeError, SystemExit) as e:
            print(f'tpu_lint: cannot resolve --jaxpr: {e}',
                  file=sys.stderr)
            return 2
        try:
            shapes = _parse_shapes(args.shapes) if args.shapes else []
        except (TypeError, ValueError) as e:
            print(f'tpu_lint: cannot parse --shapes: {e}',
                  file=sys.stderr)
            return 2
        report.extend(analysis.lint(fn, *shapes,
                                    disable=args.disable,
                                    fused_steps=args.fused))

    # one lowering memo shared by --plan and --hlo: the same
    # (target, mesh, shardings) triple is compiled exactly once no
    # matter how many surfaces ask for it.  The memo is additionally
    # backed by the PERSISTENT compile cache's text tier
    # (core.compile_cache via hlo.lower_text), so a repeated tpu_lint
    # invocation on unchanged targets reads its candidate modules off
    # disk — the stats delta below lands in --json as `cache_hits`.
    lower_cache = {}
    from paddle_tpu.core import compile_cache as _cc
    _cc_before = _cc.stats()
    plan_results = {}
    plan_error = None
    calibration = None
    if args.calibration:
        from paddle_tpu.analysis import costmodel as _costmodel
        try:
            calibration = _costmodel.load_calibration(args.calibration)
        except (OSError, ValueError) as e:
            print(f'tpu_lint: cannot load --calibration: {e}',
                  file=sys.stderr)
            return 2
    if args.plan:
        from paddle_tpu.analysis import targets as _targets_mod
        names = list(_targets_mod.TARGETS)
        if args.targets:
            names = [t.strip() for t in args.targets.split(',')
                     if t.strip()]
            unknown = [t for t in names
                       if t not in _targets_mod.TARGETS]
            if unknown:
                print(f'tpu_lint: unknown --targets {unknown} '
                      f'(have: {list(_targets_mod.TARGETS)})',
                      file=sys.stderr)
                return 2
        plan_results, plan_errors = _run_plan_suite(
            names, args.chips, hbm_gb=args.hbm_gb,
            calibration=calibration, include_pp=not args.no_pp,
            max_candidates=args.max_candidates,
            lower_cache=lower_cache)
        if plan_errors:
            plan_error = '; '.join(f'{t}: {e}'
                                   for t, e in plan_errors.items())

    hlo_reports = {}
    hlo_error = None
    if args.hlo:
        thresholds = {}
        if args.hbm_gb is not None:     # 0 is a legitimate budget
            thresholds['hbm_bytes'] = int(args.hbm_gb * (1 << 30))
        if calibration is not None:
            thresholds['calibration'] = calibration
        # inside the degrade-don't-discard region: a mesh that cannot
        # be built (e.g. a preset backend with fewer devices than the
        # forced count could create) must not throw away the AST/jaxpr
        # report already in hand
        mesh = None
        try:
            mesh = _build_mesh(mesh_axes)
        except SystemExit as e:
            hlo_error = str(e)
            print(f'{hlo_error} — --hlo audit skipped; AST/jaxpr '
                  'findings below are still valid', file=sys.stderr)
        if mesh is not None and mesh.devices.size <= 1:
            print('tpu_lint: --hlo resolved to a 1-device mesh — the '
                  'SPMD audit is vacuous (nothing is partitioned, no '
                  'collectives exist); pass --mesh, e.g. --mesh dp=8',
                  file=sys.stderr)
        # examples/ + models/ paths -> the built-in target suite (the
        # models those paths train); --jaxpr -> that callable directly.
        # Match whole path components, not substrings (tests/
        # test_models.py is NOT a models/ path).
        wants_suite = any(
            part in ('examples', 'models', 'serving')
            for p in args.paths
            for part in os.path.normpath(os.path.abspath(p))
            .split(os.sep))
        if not wants_suite and not args.jaxpr:
            print('tpu_lint: --hlo has nothing to audit for these '
                  'paths — it lowers the built-in model suite for '
                  'examples//models/ paths or a --jaxpr target; '
                  'AST/jaxpr findings below are NOT an SPMD audit',
                  file=sys.stderr)
        try:
            if wants_suite and mesh is not None:
                from paddle_tpu.analysis import targets as _tmod
                suite_reports, suite_errors = _run_hlo_suite(
                    mesh, list(_tmod.TARGETS), thresholds,
                    args.disable, lower_cache=lower_cache)
                hlo_reports.update(suite_reports)
                if suite_errors:
                    hlo_error = '; '.join(
                        f'{t}: {e}' for t, e in suite_errors.items())
            if args.jaxpr and mesh is not None:
                hlo_reports[args.jaxpr] = analysis.lint_hlo(
                    fn, *shapes, mesh=mesh, thresholds=thresholds,
                    disable=args.disable, name=f'hlo:{args.jaxpr}')
        except Exception as e:
            # do NOT discard the AST/jaxpr report already in hand: a
            # broken lower must not silently disable the rest of the
            # gate (a caller may parse stdout JSON regardless of the
            # exit code)
            hlo_error = repr(e)
            print(f'tpu_lint: --hlo audit failed: {hlo_error} — '
                  'AST/jaxpr findings below are still valid',
                  file=sys.stderr)
        for rep in hlo_reports.values():
            report.findings.extend(rep.findings)

    cache_hits = None
    if args.plan or args.hlo:
        after = _cc.stats()
        delta = lambda k: after.get(k, 0) - _cc_before.get(k, 0)  # noqa: E731
        cache_hits = {
            'persistent': delta('hit_hlo'),
            'persistent_misses': delta('miss_hlo'),
            'memo_entries': len(lower_cache),
            'enabled': _cc.enabled(),
        }
        if cache_hits['persistent']:
            print(f'tpu_lint: {cache_hits["persistent"]} lowering(s) '
                  'served from the persistent compile cache',
                  file=sys.stderr)

    if args.json:
        doc = json.loads(report.to_json())
        if args.hlo:
            doc['hlo'] = {n: json.loads(r.to_json())
                          for n, r in hlo_reports.items()}
            if hlo_error:
                doc['hlo_error'] = hlo_error
        if args.plan:
            doc['plan'] = {n: r.to_json()
                           for n, r in plan_results.items()}
            if plan_error:
                doc['plan_error'] = plan_error
        if cache_hits is not None:
            doc['cache_hits'] = cache_hits
        print(json.dumps(doc, indent=2))
    else:
        if args.paths or args.jaxpr:
            print(report.render() if report else report.summary())
        for tname, rep in hlo_reports.items():
            print(f'\n-- hlo audit [{tname}] --')
            _render_hlo_extras(rep.extras)
        for tname, res in plan_results.items():
            print()
            print(res.render())
        if cache_hits is not None and (cache_hits['persistent']
                                       or cache_hits['persistent_misses']):
            print(f'\ncompile cache: {cache_hits["persistent"]} hit / '
                  f'{cache_hits["persistent_misses"]} miss '
                  '(persistent lowering tier)')

    if hlo_error or plan_error:
        return 2
    if args.fail_on == 'never':
        return 0
    return 1 if report.at_least(args.fail_on) else 0


if __name__ == '__main__':
    sys.exit(main())
