"""paddle_tpu.analysis — jaxpr-level TPU lint (static analysis).

The paper's premise is that paddle_tpu programs compile cleanly to
XLA; nothing in a passing test suite proves a model *stays* compiled —
silent retraces, per-step host syncs, replicated giants and f32 creep
all degrade to "slow but correct".  This subsystem checks those
properties statically:

* a **jaxpr walker** (``walker``) traces the exact program XLA will
  compile (jax.make_jaxpr — no device execution) and a rule registry
  (``rules``) audits it: ``recompile-hazard``, ``host-sync``,
  ``replicated-giant``, ``amp-promotion``, ``donation-violation``,
  ``constant-capture``;
* an **AST pre-trace linter** (``ast_lint``) sweeps dy2static sources
  for host syncs the tracer would hit before a jaxpr exists;
* **runtime companions** (``runtime``): an eager dtype audit riding
  core/dispatch, and the retrace monitor compile caches report into;
* an **auto-sharding planner** (``planner``): enumerates candidate
  mesh shapes and PartitionSpec assignments for a step function,
  scores each through the lowered-HLO audit (torus-decomposed
  collective cost via ``costmodel`` + liveness peak memory vs an HBM
  budget) and returns ranked plans — ``tpu_lint --plan`` and
  ``ParallelTrainer(auto_shard=True)``.

Entry points:

    report = analysis.lint(step_fn, *example_args,
                           mesh=mesh, donate_argnums=(0, 2))
    report = analysis.lint_sources(['examples/', 'paddle_tpu/models/'])

Wired in at every compile choke point: ``jit.to_static(check=...)``,
``static.Program.lint()`` / ``Executor.run(check=...)``,
``hapi.Model.prepare(lint=...)``, ``ParallelTrainer(lint=...)``, and
the ``tools/tpu_lint.py`` CLI (the tier-1 self-lint gate).

Suppression: ``# tpu-lint: disable=rule-id`` on the flagged line (or
the enclosing ``def``), or ``disable=('rule-id',)`` on any entry
point.
"""
import functools
import os
import warnings

import jax
import jax.numpy as jnp

from .findings import (  # noqa: F401
    Finding, LintReport, LintError, LintWarning, HIGH, WARN, INFO,
    SEVERITIES)
from . import walker  # noqa: F401
from . import rules as _rules_mod
from .rules import (  # noqa: F401
    RULES, register_rule, RuleContext, DEFAULT_THRESHOLDS, run_rules,
    scalar_arg_findings)
from . import ast_lint  # noqa: F401
from .ast_lint import (  # noqa: F401
    lint_source, lint_file, lint_callable, apply_suppressions)
from .runtime import amp_audit, note_retrace, OpDtypeAudit  # noqa: F401
from . import costmodel  # noqa: F401
from . import hlo  # noqa: F401
from .hlo import (  # noqa: F401
    HLO_RULES, register_hlo_rule, DEFAULT_HLO_THRESHOLDS)
from . import targets  # noqa: F401
from . import planner  # noqa: F401
from .planner import plan_model  # noqa: F401
from . import threads  # noqa: F401
from .threads import (  # noqa: F401
    lint_threads_source, lint_threads_file, lint_threads_sources,
    THREAD_RULES, register_thread_rule)
from . import lockcheck  # noqa: F401
from .lockcheck import (  # noqa: F401
    LockChecker, resolve_lockcheck)
# importing spmd also registers its HLO collective-order rule into
# HLO_RULES, so every --hlo audit checks conditional branch parity
from . import spmd  # noqa: F401
from .spmd import (  # noqa: F401
    lint_spmd_source, lint_spmd_file, lint_spmd_sources,
    SPMD_RULES, register_spmd_rule)

# the lowered-HLO SPMD audit (post-partitioner: sharding placement,
# collective cost, per-device peak memory) — the escalation the
# compile choke points run when a Mesh is active
lint_hlo = hlo.audit


def escalate_hlo(report, fn, state_args, batch_args, mesh, *,
                 donate_argnums=(), name=None):
    """The shared choke-point posture for the mesh-gated HLO
    escalation: `state_args` replicated, `batch_args` sharded on the
    mesh's data axis when divisible (hlo.auto_shardings heuristic,
    replicated fallback), findings extend `report` in place.
    ParallelTrainer does NOT use this — it lowers with its real jit
    shardings and donation instead."""
    from jax.sharding import NamedSharding, PartitionSpec
    repl = NamedSharding(mesh, PartitionSpec())
    rep_tree = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda _: repl, t)
    batch_sh = tuple(hlo.auto_shardings(mesh, tuple(batch_args)) or
                     (rep_tree(b) for b in batch_args))
    in_sh = tuple(rep_tree(a) for a in state_args) + batch_sh
    return report.extend(lint_hlo(
        fn, *state_args, *batch_args, mesh=mesh, in_shardings=in_sh,
        donate_argnums=donate_argnums,
        global_shapes=getattr(report, 'global_big_shapes', None),
        name=name))


__all__ = ['lint', 'lint_sources', 'lint_layer', 'lint_hlo',
           'escalate_hlo', 'emit',
           'safe_emit',
           'Finding', 'LintReport', 'LintError', 'LintWarning',
           'HIGH', 'WARN', 'INFO', 'RULES', 'register_rule',
           'RuleContext', 'run_rules', 'DEFAULT_THRESHOLDS',
           'scalar_arg_findings', 'HLO_RULES', 'register_hlo_rule',
           'DEFAULT_HLO_THRESHOLDS',
           'lint_source', 'lint_file', 'lint_callable',
           'apply_suppressions', 'amp_audit', 'note_retrace',
           'walker', 'ast_lint', 'hlo', 'costmodel', 'targets',
           'planner', 'plan_model',
           'threads', 'lint_threads_source', 'lint_threads_file',
           'lint_threads_sources', 'THREAD_RULES',
           'register_thread_rule', 'lockcheck', 'LockChecker',
           'resolve_lockcheck',
           'spmd', 'lint_spmd_source', 'lint_spmd_file',
           'lint_spmd_sources', 'SPMD_RULES', 'register_spmd_rule']


def _leaf_ranges(example_args):
    """Flat-invar index range each positional arg occupies."""
    ranges = []
    start = 0
    for a in example_args:
        n = len(jax.tree_util.tree_leaves(a))
        ranges.append((start, start + n))
        start += n
    return ranges


def lint(fn, *example_args, mesh=None, donate_argnums=(), disable=(),
         signatures=None, thresholds=None, name=None, source=True,
         fused_steps=None, **example_kwargs):
    """Trace `fn` abstractly and run every registered jaxpr rule.

    example_args: concrete arrays / pytrees / jax.ShapeDtypeStruct
    placeholders — Python scalars are recorded as recompile hazards
    and traced as arrays so the walk still completes.
    mesh: active jax.sharding.Mesh (enables replicated-giant).
    donate_argnums: positions the real jit call donates (enables
    donation-violation).
    signatures: optional list of per-call shape tuples the step has
    already seen (enables the shape-variance hazard).
    source: additionally AST-lint `fn`'s own source when retrievable.

    Returns a LintReport; raises nothing — gate with
    report.raise_for('high') or analysis.emit(report, 'error').
    """
    name = name or getattr(fn, '__name__', None) or 'step'
    python_scalars = []
    traced_args = []
    for i, a in enumerate(example_args):
        if isinstance(a, (bool, int, float)):
            python_scalars.append((i, a))
            traced_args.append(jnp.asarray(a))
        else:
            traced_args.append(a)
    findings = []
    closed = None
    try:
        closed = walker.trace_jaxpr(fn, *traced_args, **example_kwargs)
    except (jax.errors.TracerBoolConversionError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError) as e:
        # the trace itself hit a host materialization — that IS the
        # host-sync finding, with jax's own diagnosis attached
        first = str(e).strip().split('\n')[0]
        findings.append(Finding(
            'host-sync', HIGH,
            f'tracing {name} aborted on a host materialization of a '
            f'traced value: {first}',
            origin='jaxpr'))
    if closed is not None:
        ctx = RuleContext(
            closed, mesh=mesh, donate_argnums=donate_argnums,
            arg_leaf_ranges=_leaf_ranges(traced_args),
            python_scalars=python_scalars, signatures=signatures,
            thresholds=thresholds, name=name, fused_steps=fused_steps)
        findings.extend(run_rules(ctx, disable=disable))
    if source:
        findings.extend(lint_callable(fn, disable=disable))
    findings = [f for f in apply_suppressions(findings)
                if f.rule not in disable]
    report = LintReport(findings, name=name)
    if closed is not None:
        # thunk, NOT extras: a set of tuples is side data for the HLO
        # escalation (lint_hlo(global_shapes=...) skips its second
        # abstract trace), and only the mesh-gated escalation reads it
        # — the common single-device path never pays the extra walk
        thr = (thresholds or {}).get(
            'replicated_bytes',
            DEFAULT_HLO_THRESHOLDS['replicated_bytes'])
        report._big_shapes_thunk = functools.partial(
            hlo.global_big_shapes_of, closed, thr)
    return report


def _iter_py_files(paths):
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if f.endswith('.py'):
                        yield os.path.join(root, f)
        elif p.endswith('.py'):
            yield p


def lint_sources(paths, scope='traced', disable=()):
    """AST-lint .py files / directories (no imports, no execution).
    This is what tools/tpu_lint.py and the tier-1 self-lint gate
    run over examples/ and paddle_tpu/models/."""
    findings = []
    for path in _iter_py_files(paths):
        findings.extend(lint_file(path, scope=scope, disable=disable))
    findings = [f for f in findings if f.rule not in disable]
    return LintReport(findings, name='sources')


def lint_layer(layer, disable=()):
    """AST-lint a Layer's forward (and its direct sublayers' forwards)
    — the pre-trace half of Model.prepare(lint=...)."""
    seen, findings = set(), []

    def one(lyr):
        cls = type(lyr)
        if cls in seen:
            return
        seen.add(cls)
        fwd = getattr(cls, 'forward', None)
        if fwd is not None and 'paddle_tpu/nn/' not in (
                getattr(fwd, '__code__', None) and
                fwd.__code__.co_filename or ''):
            findings.extend(lint_callable(fwd, disable=disable))

    one(layer)
    for _name, sub in getattr(layer, 'named_sublayers', lambda: [])():
        one(sub)
    findings = [f for f in findings if f.rule not in disable]
    return LintReport(findings, name=type(layer).__name__)


def emit(report, mode='warn'):
    """Standard surfacing for the compile-choke-point integrations.

    mode: falsy -> silent; 'warn'/True -> one LintWarning per report;
    'error' -> LintError on any high-severity finding (lower ones
    still warn).  Findings additionally land as telemetry
    ``lint_finding`` events (countable per run) regardless of
    warn/error mode."""
    if not mode or not report:
        return report
    _telemetry_findings(report)
    if mode == 'error' and report.high:
        raise LintError(report.render(report.high), report=report)
    warnings.warn(str(report), LintWarning, stacklevel=3)
    return report


def _telemetry_findings(report):
    """One ``lint_finding`` telemetry event per finding (never
    raises — telemetry must not break a compile)."""
    try:
        from .. import telemetry
        for f in report:
            telemetry.event('lint_finding', rule=f.rule,
                            severity=f.severity, file=f.file,
                            line=f.line, origin=f.origin,
                            name=report.name)
            telemetry.add(f'lint.{f.severity}')
    except Exception:       # pragma: no cover - defensive
        pass


def safe_emit(build_report, mode):
    """emit() under the integration contract shared by every compile
    choke point (to_static / Model.prepare / ParallelTrainer /
    Executor): `build_report` (a zero-arg callable returning a
    LintReport) plus emit() run guarded — only LintError, the
    'error'-mode verdict, escapes; an analyzer crash degrades to a
    LintWarning instead of breaking the user's compile."""
    if not mode:
        return None
    try:
        return emit(build_report(), mode)
    except LintError:
        raise
    except Exception as e:        # pragma: no cover - analyzer bug
        warnings.warn(f'tpu-lint skipped ({e!r})', LintWarning,
                      stacklevel=3)
        return None
