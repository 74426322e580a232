"""Property-based chaos plan generation + failing-plan shrinking.

Hand-written FaultPlans prove the failure modes someone thought of.
Long soaks need the other kind: arbitrary LEGAL compositions of faults
(seeded, so any failure replays exactly), run against the invariant
set I1-I7 until something breaks — and when it does, a plan of a dozen
interleaved faults is useless as a bug report.  This module does both
halves:

* :func:`generate_plan` — a seeded generator that composes faults
  respecting each seam's PRECONDITIONS (a shard-corruption fault needs
  a checkpoint to exist, collective faults need >1 process, process
  faults must land inside the step range, a hang must outlast the
  collective timeout so it is a hang and not a delay).  A `require`
  set guarantees coverage classes (the soak acceptance gate wants at
  least one hung collective, one killed worker, one torn checkpoint
  in every default soak).  Same (seed, steps, procs) => the identical
  plan, fault for fault.
* :func:`shrink` — delta-debugging over the fault list: greedily drop
  halves, then single faults, while the failure predicate keeps
  failing; the fixed point is a minimal reproducer.
* :func:`emit_regression` — write the shrunk plan as a ready-to-commit
  pytest case (slow-marked) so the reproducer survives the incident.

tools/soak_run.py drives all three.
"""
import hashlib
import json
import random

from .chaos import (Fault, FaultPlan, COLLECTIVE_FAULT_KINDS,
                    SERVING_FAULT_KINDS)

__all__ = ['GENERATABLE_KINDS', 'OPTIN_KINDS', 'generate_plan',
           'legal', 'shrink', 'plan_fingerprint', 'emit_regression']

# kinds the generator composes.  nan_grads is excluded (the soak
# workload has no gradient path), delete/stale_heartbeat are excluded
# (the multi-process topology heartbeats through the KV store, not the
# legacy file).
GENERATABLE_KINDS = (
    'sigkill', 'sigterm', 'torn_write', 'drop_commit', 'io_error',
    'slow_io', 'slow_rank',
) + COLLECTIVE_FAULT_KINDS

# opt-in coverage-class kinds: legal() admits them but the DEFAULT
# pool never draws them — growing GENERATABLE_KINDS would shift every
# seeded draw stream and silently break golden-pinned plans.  'drift'
# is the supervisor-migration class (generate_plan(supervisor=True));
# 'collective_skip' is the SPMD-contract-violation class the
# collective flight recorder attributes (pass kinds= explicitly);
# the SERVING_FAULT_KINDS are the fleet-drill class
# (ServingFaultInjector) — their drills have no
# training step, so their clock is stream progress (after_tokens).
OPTIN_KINDS = ('drift', 'collective_skip') + SERVING_FAULT_KINDS


def legal(fault, steps, procs, save_every=2, hang_min_s=None):
    """True iff `fault` respects its seam's preconditions for a soak
    of `steps` steps over `procs` processes.  The generator only emits
    legal faults; the shrinker preserves legality by construction
    (removing faults cannot violate a precondition)."""
    f = fault if isinstance(fault, Fault) else Fault.from_dict(fault)
    if f.kind not in GENERATABLE_KINDS + OPTIN_KINDS:
        return False
    if f.rank is not None and not (0 <= int(f.rank) < procs):
        return False
    in_range = f.at_step is None or (2 <= f.at_step <= steps)
    if f.kind in SERVING_FAULT_KINDS:
        # serving faults are clocked by stream progress, not steps:
        # need an after_tokens mark and a bounded count (an unbounded
        # replica_kill would murder every promoted spare in turn);
        # `procs` is the replica count for replica-addressed kinds
        if f.after_tokens is None or f.after_tokens < 0 \
                or f.count is None:
            return False
        if f.kind in ('replica_kill', 'replica_hang'):
            return f.rank is None or 0 <= int(f.rank) < procs
        return True
    if f.kind == 'drift':
        # the synthetic sensor edge must land on rank 0 — the plan
        # supervisor actuator subscribes to rank 0's recorder; drift
        # injected anywhere else never reaches it
        return in_range and f.at_step is not None and f.rank == 0
    if f.kind in ('sigkill', 'sigterm'):
        # process faults fire from the step loop: need a live step, an
        # addressed rank (an unaddressed kill would fire on EVERY rank
        # — that is cluster murder, not a fault), and a step AFTER the
        # first save so the restart exercises restore, not a cold
        # start
        return (in_range and f.at_step is not None
                and f.rank is not None and f.at_step > save_every)
    if f.kind == 'slow_rank':
        return in_range and f.at_step is not None and f.rank is not None
    if f.kind == 'collective_skip':
        # same wire preconditions as the COLLECTIVE_FAULT_KINDS seams
        # plus a bounded count: an unbounded skip would re-fire on
        # every post-restart replay and the run would never converge
        return (procs >= 2 and f.rank is not None and in_range
                and f.at_step is not None and f.count is not None)
    if f.kind in COLLECTIVE_FAULT_KINDS:
        # collective faults need a wire: >1 process, an addressed rank
        # (the sequence must be attributable), a step inside the range;
        # a hang must outlast the collective timeout or it is a delay
        if procs < 2 or f.rank is None or not in_range \
                or f.at_step is None:
            return False
        if f.kind == 'collective_hang' and hang_min_s is not None \
                and f.delay_s < hang_min_s:
            return False
        return True
    if f.kind in ('torn_write', 'drop_commit'):
        # checkpoint-seam faults need a save to exist: the step they
        # target must be a save step
        if f.kind == 'drop_commit':
            return f.at_step is not None and in_range \
                and f.at_step % save_every == 0
        return f.path is not None and f.path.startswith('step_')
    if f.kind in ('io_error', 'slow_io'):
        return f.prob is not None and 0 < f.prob <= 1
    return in_range


def _make(kind, rng, steps, procs, save_every, hang_s):
    """One legal fault of `kind`, drawn from the plan RNG."""
    step = rng.randrange(2, max(3, steps + 1))
    rank = rng.randrange(procs)
    if kind in ('sigkill', 'sigterm'):
        lo = min(save_every + 1, steps)
        return Fault(kind, at_step=rng.randrange(lo, steps + 1),
                     rank=rank)
    if kind == 'drift':
        lo = min(save_every + 1, steps)
        return Fault(kind, at_step=rng.randrange(lo, steps + 1),
                     rank=0, op='all-reduce',
                     us_ratio=round(rng.uniform(6.0, 12.0), 2))
    if kind == 'slow_rank':
        return Fault(kind, at_step=step, rank=rank,
                     delay_s=round(rng.uniform(0.2, 0.8), 3))
    if kind == 'collective_hang':
        return Fault(kind, at_step=step, rank=rank, delay_s=hang_s)
    if kind == 'collective_delay':
        return Fault(kind, at_step=step, rank=rank,
                     delay_s=round(rng.uniform(0.05, 0.3), 3))
    if kind in ('collective_drop', 'collective_corrupt'):
        return Fault(kind, at_step=step, rank=rank)
    if kind == 'collective_skip':
        return Fault(kind, at_step=step, rank=rank, count=1)
    if kind == 'torn_write':
        save_step = save_every * rng.randrange(
            1, max(2, steps // save_every + 1))
        # bounded: tear one save attempt (shard + intent) and let the
        # replayed save commit — an unbounded tear would also make the
        # injected sequence depend on the incarnation count
        return Fault(kind, path=f'step_{save_step}', count=2)
    if kind == 'drop_commit':
        save_step = save_every * rng.randrange(
            1, max(2, steps // save_every + 1))
        return Fault(kind, at_step=save_step)
    if kind == 'io_error':
        return Fault(kind, prob=round(rng.uniform(0.05, 0.2), 3),
                     count=2, path='_PADDLE_2PC',
                     errno_name=rng.choice(('EIO', 'ENOSPC')))
    if kind == 'slow_io':
        return Fault(kind, prob=round(rng.uniform(0.1, 0.3), 3),
                     count=3, delay_s=0.05)
    raise ValueError(kind)


def generate_plan(seed, steps, procs, n_faults=6,
                  require=('collective_hang', 'sigkill', 'torn_write'),
                  save_every=2, hang_s=60.0, kinds=None,
                  name=None, quant_wire=False, supervisor=False):
    """A seeded, legal FaultPlan for one soak.

    `require` kinds are always present (coverage classes the soak
    gate demands); the rest are drawn from `kinds` (default
    GENERATABLE_KINDS, minus requirements already satisfied).  Pure in
    (seed, steps, procs, knobs): the same call composes the identical
    plan, which is what makes a soak failure replayable before it is
    even shrunk.

    ``quant_wire`` is the quantized-wire COVERAGE CLASS: the plan is
    tagged ``+qwire`` and tools/soak_run.py runs the workers' host
    all-reduces on the block-scaled int8 wire
    (``HostCollectives.allreduce(quant='int8')``), so every injected
    fault — corrupt-after-crc, SIGKILL mid-allreduce, hangs — drives
    the QUANTIZED payload path.  It changes no fault draw: the same
    seed composes the identical fault sequence either way (so a
    quantized soak failure bisects cleanly against its full-width
    twin).

    ``supervisor`` is the supervisor-MIGRATION coverage class (plan
    tagged ``+sup``): an injected ``drift`` fault on rank 0 — the
    synthetic sensor edge the plan supervisor actuates on — plus a
    SIGKILL landing ONE STEP after it, i.e. inside the window where
    the reshape request is written but the coordinated restart has
    not completed.  The gate it feeds: the request survives the
    crash, the cluster reshapes exactly once, no max_restarts burn,
    finals stay bit-exact.  The extra draws happen AFTER the require
    loop and only when armed, so ``supervisor=False`` plans (and
    their golden fingerprints) are byte-identical to before."""
    # int-folded so the draw stream is pure in (seed, steps, procs)
    # (random.Random rejects tuples)
    rng = random.Random(int(seed) * 1_000_003
                        + int(steps) * 1_009 + int(procs))
    pool = tuple(kinds or GENERATABLE_KINDS)
    faults = []
    seen = set()

    def admit(f):
        key = (f.kind, f.at_step, f.rank, f.path, f.op)
        if key in seen:
            return False
        if not legal(f, steps, procs, save_every=save_every):
            return False
        seen.add(key)
        faults.append(f)
        return True

    for kind in require:
        for _ in range(64):
            if admit(_make(kind, rng, steps, procs, save_every,
                           hang_s)):
                break
        else:
            raise RuntimeError(
                f'could not compose a legal {kind!r} fault for '
                f'steps={steps} procs={procs}')
    if supervisor:
        drift = None
        for _ in range(64):
            f = _make('drift', rng, steps, procs, save_every, hang_s)
            if admit(f):
                drift = f
                break
        if drift is None:
            raise RuntimeError(
                f'could not compose a legal drift fault for '
                f'steps={steps} procs={procs}')
        # the mid-migration crash: one step after the sensor edge
        admit(Fault('sigkill', rank=rng.randrange(procs),
                    at_step=min(steps, drift.at_step + 1)))
    while len(faults) < n_faults:
        kind = pool[rng.randrange(len(pool))]
        for _ in range(64):
            if admit(_make(kind, rng, steps, procs, save_every,
                           hang_s)):
                break
        else:
            break       # pool exhausted at this size; plan stays legal
    base = name or f'soak-{seed}'
    if quant_wire:
        base += '+qwire'
    if supervisor:
        base += '+sup'
    return FaultPlan(seed=seed, faults=faults, name=base)


def plan_fingerprint(plan):
    """Stable sha256 of a plan's canonical JSON — what the golden
    fixture pins so neither the generator nor the shrinker can drift
    silently."""
    return hashlib.sha256(plan.to_json().encode('utf-8')).hexdigest()


def shrink(plan, failing, max_runs=64, log=None):
    """Minimize a failing plan: returns (shrunk_plan, runs_used).

    `failing(FaultPlan) -> bool` is the oracle (True = still fails —
    for a soak, "some invariant still violated").  Delta debugging:
    drop contiguous halves first (cheap big cuts), then single faults,
    to a fixed point.  The oracle's own determinism comes from the
    plan seed — the same candidate plan replays the same run.  Caller
    note: each oracle call may be a full cluster run; `max_runs`
    bounds the bill."""
    faults = list(plan.faults)
    runs = 0

    def plan_with(fs):
        return FaultPlan(
            seed=plan.seed,
            faults=[Fault.from_dict(f.to_dict()) for f in fs],
            name=f'{plan.name or "plan"}-shrunk')

    def still_fails(fs):
        nonlocal runs
        runs += 1
        ok = failing(plan_with(fs))
        if log:
            log(f'shrink probe {runs}: {len(fs)} fault(s) -> '
                f'{"still fails" if ok else "passes"}')
        return ok

    if not still_fails(faults):
        raise ValueError('shrink() needs a failing plan: the oracle '
                         'passed on the full plan')
    # big cuts first (halves, quarters, ...), then single faults to a
    # fixed point
    chunk = max(1, len(faults) // 2)
    while runs < max_runs:
        i, progressed = 0, False
        while i < len(faults) and runs < max_runs:
            cand = faults[:i] + faults[i + chunk:]
            if cand and still_fails(cand):
                faults = cand
                progressed = True
            else:
                i += chunk
        if chunk > 1:
            chunk //= 2
        elif not progressed:
            break
    return plan_with(faults), runs


REGRESSION_TEMPLATE = '''\
"""Auto-generated chaos regression (tools/soak_run.py --emit-regression).

A property-based soak found an invariant violation; this is the
SHRUNK minimal reproducer.  Same seed => same injected sequence.
Violated: {violations}
"""
import json

import pytest

from paddle_tpu.resilience.chaos import ChaosCluster, FaultPlan

PLAN_JSON = r"""{plan_json}"""


@pytest.mark.slow
@pytest.mark.faultinject
def test_shrunk_chaos_plan_regression(tmp_path):
    plan = FaultPlan.from_json(PLAN_JSON)
    report = ChaosCluster(procs={procs}, plan=plan, steps={steps},
                          workdir=str(tmp_path / 'soak'),
                          collective_timeout_s={collective_timeout_s},
                          deadline_s={deadline_s}).run()
    assert report['ok'], json.dumps(report['violations'], indent=1)
'''


def emit_regression(plan, path, procs, steps, violations=(),
                    collective_timeout_s=15.0, deadline_s=240.0):
    """Write the shrunk plan as a ready-to-commit pytest case (slow-
    marked: it spins a real multi-process cluster).  The test asserts
    the invariants HOLD — committing it pins the fix."""
    text = REGRESSION_TEMPLATE.format(
        plan_json=plan.to_json(),
        procs=int(procs), steps=int(steps),
        collective_timeout_s=float(collective_timeout_s),
        deadline_s=float(deadline_s),
        violations='; '.join(str(v) for v in violations)[:400]
        or '(see soak report)')
    with open(path, 'w') as f:
        f.write(text)
    return path
