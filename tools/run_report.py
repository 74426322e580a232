#!/usr/bin/env python
"""run_report — merge per-host telemetry JSONL into one run report.

The telemetry layer (paddle_tpu.telemetry) streams rank-tagged events
to one ``telemetry-r<rank>.jsonl`` per host; resilience additionally
drops ``flightrec-*.json`` flight-recorder dumps next to checkpoints.
This CLI merges all of them and reconstructs what happened:

    python tools/run_report.py <dir>            # human report
    python tools/run_report.py <dir> --json     # bench/CI schema
    python tools/run_report.py a.jsonl b.jsonl  # explicit files

Report sections:
  * step-time percentiles per loop tag (p50/p90/p99, from the
    accumulators' flushed ``steps`` events);
  * compile: total seconds + per-name breakdown, retrace count;
  * device-step vs host-wait split (step_time vs dataloader wait);
  * collectives census (per-op calls/bytes, when a mesh step emitted
    one) side by side with the compile-time COST-MODEL PREDICTION
    (``collective_cost`` events: torus wire bytes + alpha-beta time
    estimate per op — analysis.costmodel) and, when a chip session
    profiled them, OBSERVED per-collective timings
    (``collective_observed`` events — the fit input for
    tools/calibrate_costmodel.py);
  * the auto-sharding plan (``plan_selected``): which (mesh,
    PartitionSpec) candidate the planner chose and its predicted
    wire/peak numbers joined against the observed census — every
    auto-sharded run reports predicted-vs-actual for the plan that
    was picked;
  * the serving section (``serve_step``/``serve_request`` joined):
    TTFT/TPOT percentiles, tokens/s, eviction/preemption counts by
    cause, per-request timeline rows, lifecycle traces
    (``serve_trace``) and any ``slo_breach``/``drift_detected``
    monitor alerts;
  * the resilience event timeline (preemption, nan_skip/rollback,
    checkpoint save/commit/restore/quarantine, SLO breaches and
    drift detections) in wall-clock order.

Multi-host merges: per-host wall clocks drift (pods give no NTP
guarantee), so each host's events are re-anchored to its first
``steps`` event before ordering — SPMD stepping is lockstep, making
that the one cross-host moment the streams share.  The applied
offsets land in ``clock_skew``; anchoring is skipped when any host
never stepped (nothing trustworthy to anchor on).

``--json`` emits one stable dict (schema_version 1, additively
extended) that CI consumes; tests/test_event_telemetry.py
schema-checks it.

Stdlib-only on purpose: it must run on a dev machine against JSONL
scraped off a dead worker, with no jax install.
"""
import argparse
import glob
import json
import os
import sys
import time

SCHEMA_VERSION = 1

RESILIENCE_KINDS = (
    'preemption', 'nan_skip', 'nan_rollback', 'nan_fatal',
    'checkpoint_save', 'checkpoint_commit', 'checkpoint_restore',
    'checkpoint_quarantine', 'flight_dump', 'crash',
    'commit_intent', 'commit_finalize', 'reshape_restore',
    'retry', 'restart_backoff', 'fault_injected',
    # watchdog / collective-layer supervision (PR 10): blown deadlines,
    # straggler attribution, lost heartbeat quorum, cluster aborts —
    # each row carries its rank, so a merged multi-host timeline shows
    # WHO hung and who merely waited
    'timeout', 'straggler', 'quorum_lost', 'coordinated_abort',
    # rolling SLO/drift monitors (telemetry.monitors): an SLO breach
    # or a predicted-vs-observed drift detection belongs on the same
    # timeline as the failures it predicts
    'slo_breach', 'drift_detected',
    # live cluster-view edges (telemetry.cluster monitors): who the
    # joined view blamed, and when the per-rank losses split
    'straggler_suspect', 'rank_divergence',
    # a fused K-chunk that exceeded the armed watchdog budget
    'fused_clamp',
    # the self-healing actuator (resilience.supervisor): how each
    # incident terminated (swap/hold/backoff/degraded + stage), and
    # the applied plan swap itself — the observe->act loop's act half
    # belongs on the same timeline as the sensor edges that caused it
    'remediation', 'plan_swap',
    # memory observatory (telemetry.memory + MemoryMonitor): live
    # bytes crossed the budget watermark — the edge the supervisor
    # re-plans on with a tightened hbm_budget_gb
    'memory_pressure',
    # collective flight recorder (distributed.collective): the first
    # divergent collective across ranks, with trigger/op/step/ranks
    # and per-rank call sites — the attributed refinement of a
    # generic timeout or rank_divergence
    'collective_mismatch')

# spans (kind='span', name=...) that belong on the resilience
# timeline: the 2-phase commit barrier wait and the restore itself
RESILIENCE_SPAN_NAMES = ('commit_barrier', 'checkpoint_restore')

# -- the EVENT_KINDS coverage contract ----------------------------------------
# telemetry.recorder.EVENT_KINDS is the emission vocabulary; this pair
# is the CONSUMPTION side.  The recorder meta-test asserts every
# declared kind is either in RENDERED_KINDS (analyze() reads it into a
# report section / the timeline) or in IGNORED_KINDS with a written
# reason — so an event can never again be emitted and silently dropped
# (the PR-12 serve_step/serve_request bug, prevented structurally).
RENDERED_KINDS = RESILIENCE_KINDS + (
    'steps',                # step-time percentiles / split / scalars
    'compile', 'retrace',   # compile section
    'compile_cache',        # cache section
    'collectives', 'collective_cost', 'collective_observed',
    'plan_selected',        # plan section
    'profile_capture',      # profile section
    'serve_step', 'serve_request', 'serve_trace',  # serving section
    'serve_reject',         # serving section: admission shed trail
    'fleet_event',          # serving section: router control plane
    'lint_finding',         # lint section
    'span',                 # spans table + resilience span rows
    'memory_compiled',      # memory section: per-module three-way rows
    'memory_sample',        # memory section: live sampler ticks
)
IGNORED_KINDS = {
    'run_meta': 'per-run header (argv/rank/backend): provenance '
                'metadata, not a report row',
    'scalar': 'user scalar stream — consumed by the TensorBoard/'
              'VisualDL exporters, not the merged report',
    'lockcheck': 'runtime lock-checker disarm summary (cycles/'
                 'violations/hold stats): a debug diagnostic read '
                 'directly from its own report(), not a run row',
}


def _median(vals):
    """Proper even-count median (two-rank clusters must not anchor
    the skew baseline on the slower rank)."""
    if not vals:
        return None
    vs = sorted(vals)
    n = len(vs)
    return vs[n // 2] if n % 2 else 0.5 * (vs[n // 2 - 1] + vs[n // 2])


def _percentiles(times_ms):
    if not times_ms:
        return {}
    ts = sorted(times_ms)
    n = len(ts)

    def pct(q):
        return round(ts[min(n - 1, int(n * q))], 4)

    return {'steps': n,
            'mean_ms': round(sum(ts) / n, 4),
            'p50_ms': pct(0.50), 'p90_ms': pct(0.90),
            'p99_ms': pct(0.99), 'max_ms': round(ts[-1], 4)}


def discover(paths):
    """Expand dirs/files into (jsonl_files, flightrec_files)."""
    jsonls, flights = [], []
    for p in paths:
        if os.path.isdir(p):
            jsonls += sorted(glob.glob(
                os.path.join(p, 'telemetry-*.jsonl')))
            jsonls += sorted(glob.glob(
                os.path.join(p, '**', 'telemetry-*.jsonl'),
                recursive=True))
            flights += sorted(glob.glob(
                os.path.join(p, '**', 'flightrec-*.json'),
                recursive=True))
        elif p.endswith('.jsonl'):
            jsonls.append(p)
        elif p.endswith('.json'):
            flights.append(p)
    # de-dup while keeping order (dir glob may double-match)
    seen = set()
    jsonls = [f for f in jsonls
              if not (f in seen or seen.add(f))]
    return jsonls, flights


def load_events(jsonl_files, flight_files):
    """All events from every source, plus per-file metadata.
    Flight dumps contribute their embedded event rings (rank-tagged
    from the dump header); duplicate (ts, kind, rank) records — an
    event both streamed and ring-dumped — collapse to one."""
    events, sources = [], []
    for f in jsonl_files:
        n = 0
        with open(f) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue        # torn final line of a dead worker
                if isinstance(rec, dict) and 'kind' in rec:
                    rec.setdefault('rank', 0)
                    events.append(rec)
                    n += 1
        sources.append({'file': f, 'records': n, 'type': 'jsonl'})
    for f in flight_files:
        try:
            with open(f) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        rank = doc.get('rank', 0)
        n = 0
        for rec in doc.get('events', []):
            if isinstance(rec, dict) and 'kind' in rec:
                rec = dict(rec)
                rec.setdefault('rank', rank)
                events.append(rec)
                n += 1
        sources.append({'file': f, 'records': n, 'type': 'flightrec',
                        'counters': doc.get('counters', {})})
    seen = set()
    out = []
    for e in events:
        # monotonic 't' joins the key so two DISTINCT same-kind events
        # in the same rounded microsecond survive; a record that was
        # both streamed and ring-dumped shares all four fields
        k = (e.get('ts'), e.get('t'), e.get('kind'), e.get('rank'))
        if k in seen:
            continue
        seen.add(k)
        out.append(e)
    skew = normalize_clock_skew(out)
    out.sort(key=lambda e: e.get('ts') or 0)
    return out, sources, skew


def normalize_clock_skew(events):
    """Anchor each host's wall clock to its first ``steps`` event.

    ts is per-host wall-clock; hosts drift by seconds on real pods,
    which used to mis-order the merged resilience timeline (a rank-1
    preemption could sort before the rank-0 steps that preceded it).
    SPMD training steps in lockstep, so the first flushed ``steps``
    event is the one instant every host's stream shares: shift each
    rank by (its anchor - earliest anchor).  Mutates ts in place and
    returns {rank: applied_offset_s}; skipped (returns {}) unless at
    least two ranks exist and EVERY rank emitted steps events — a
    host that never stepped has no trustworthy anchor."""
    anchors = {}
    ranks = set()
    for e in events:
        r = e.get('rank', 0)
        ranks.add(r)
        ts = e.get('ts')
        if e.get('kind') == 'steps' and ts is not None:
            if r not in anchors or ts < anchors[r]:
                anchors[r] = ts
    if len(ranks) < 2 or set(anchors) != ranks:
        return {}
    base = min(anchors.values())
    offsets = {r: round(a - base, 6) for r, a in anchors.items()}
    if not any(offsets.values()):
        return {}
    for e in events:
        off = offsets.get(e.get('rank', 0))
        if off and e.get('ts') is not None:
            e['ts'] = round(e['ts'] - off, 6)
    return offsets


def analyze(events, sources, skew=None):
    """The merged run report as one dict (the --json schema)."""
    by_kind = {}
    for e in events:
        by_kind.setdefault(e['kind'], []).append(e)

    # -- step-time percentiles + host-wait split per loop tag ----
    step_stats, split = {}, {}
    scalars_last = {}
    total_steps = 0
    for ev in by_kind.get('steps', ()):
        tag = ev.get('tag', 'train')
        st = step_stats.setdefault(tag, {'times_ms': [], 'waits_ms': [],
                                         'n': 0})
        st['n'] += ev.get('n', 0)
        total_steps += ev.get('n', 0)
        st['times_ms'] += [t for t in ev.get('step_time_ms') or []
                           if t is not None]
        st['waits_ms'] += [w for w in ev.get('wait_ms') or []
                           if w is not None]
        for k, col in ev.items():
            if k in ('kind', 'ts', 't', 'rank', 'tag', 'n', 'step',
                     'step_lo', 'step_hi', 'step_time_ms', 'wait_ms'):
                continue
            if isinstance(col, list) and col:
                vals = [v for v in col if v is not None]
                if vals:
                    scalars_last.setdefault(tag, {})[k] = vals[-1]
    steps_out = {}
    for tag, st in step_stats.items():
        steps_out[tag] = _percentiles(st['times_ms'])
        steps_out[tag]['count'] = st['n']
        dev_ms = sum(st['times_ms'])
        wait_ms = sum(st['waits_ms'])
        if dev_ms or wait_ms:
            tot = dev_ms + wait_ms
            split[tag] = {
                'device_step_ms': round(dev_ms, 3),
                'host_wait_ms': round(wait_ms, 3),
                'host_wait_frac': round(wait_ms / tot, 6) if tot else 0.0,
            }

    # -- compile / retrace ---------------------------------------
    compile_events = by_kind.get('compile', [])
    per_name = {}
    for e in compile_events:
        row = per_name.setdefault(e.get('name', '?'),
                                  {'count': 0, 'total_s': 0.0})
        row['count'] += 1
        row['total_s'] = round(row['total_s'] + (e.get('dur_s') or 0.0),
                               6)
    compile_out = {
        'count': len(compile_events),
        'total_s': round(sum(e.get('dur_s') or 0.0
                             for e in compile_events), 6),
        'per_name': per_name,
    }
    retraces = by_kind.get('retrace', [])
    retrace_out = {'count': len(retraces)}
    if retraces:
        worst = max(retraces, key=lambda e: e.get('variants', 0))
        retrace_out['max_variants'] = worst.get('variants')
        retrace_out['worst'] = worst.get('name')

    # -- persistent compile cache: hit rate + compile time saved --
    cc_events = by_kind.get('compile_cache', [])
    compile_cache = None
    if cc_events:
        actions = {}
        per_name = {}
        for e in cc_events:
            a = e.get('action', '?')
            row = actions.setdefault(a, {'count': 0, 'bytes': 0})
            row['count'] += 1
            row['bytes'] += e.get('bytes') or 0
            nm = per_name.setdefault(e.get('name', '?'),
                                     {'hits': 0, 'misses': 0})
            # 'deserialize' refines a 'hit' (same lookup), so only
            # hit/miss count toward the rate — one event per lookup
            if a == 'hit':
                nm['hits'] += 1
            elif a == 'miss':
                nm['misses'] += 1
        hits = actions.get('hit', {}).get('count', 0)
        misses = actions.get('miss', {}).get('count', 0)
        lookups = hits + misses
        compile_cache = {
            'actions': actions,
            'hits': hits,
            'misses': misses,
            'lookups': lookups,
            'hit_rate': round(hits / lookups, 4) if lookups else None,
            'deserialized': actions.get('deserialize',
                                        {}).get('count', 0),
            'serialized': actions.get('serialize', {}).get('count', 0),
            'quarantined': actions.get('quarantine',
                                       {}).get('count', 0),
            'warm_start_entries': sum(e.get('count') or 0
                                      for e in cc_events
                                      if e.get('action') == 'warm_start'),
            'compile_time_saved_s': round(sum(
                e.get('saved_s') or 0.0 for e in cc_events
                if e.get('action') == 'deserialize'), 6),
            'per_name': per_name,
        }

    # -- collectives: observed census vs compile-time prediction --
    coll = by_kind.get('collectives', [])
    collectives = None
    if coll:
        last = coll[-1]
        collectives = {'per_op': last.get('per_op', {}),
                       'total_bytes': last.get('total_bytes', 0),
                       'mesh': last.get('mesh')}
    cost = by_kind.get('collective_cost', [])
    collectives_predicted = None
    if cost:
        last = cost[-1]
        collectives_predicted = {
            'per_op': last.get('per_op', {}),
            'wire_bytes_total': last.get('wire_bytes_total', 0),
            'est_us_total': last.get('est_us_total', 0.0),
            'quant_collectives': last.get('quant_collectives'),
            'mesh': last.get('mesh')}
    # profiled per-collective timings (telemetry.profile capture
    # windows): the observed side calibrate_costmodel.py fits
    # alpha/beta from.  Events carry per-CALL us, one event per call
    # site (instr) per window — average each call site across windows,
    # then sum call sites per op, so the per-op observed_us is a
    # per-step total comparable to the census est_us no matter how
    # many windows the run captured.
    per_instr = {}
    for e in by_kind.get('collective_observed', ()):
        op = e.get('op')
        if op is None:
            continue
        # `name` (the emitting loop) joins the key: two trainers in
        # one run may reuse an instr name across different compiled
        # modules — their per-call timings must not blend
        key = (op, e.get('name'), e.get('instr'))
        r = per_instr.setdefault(
            key, {'us': [], 'wire_bytes': 0, 'phases': 0, 'calls': 0,
                  'wire_dtype': None})
        r['us'].append(e.get('us') or 0.0)
        r['wire_bytes'] = max(r['wire_bytes'],
                              e.get('wire_bytes') or 0)
        r['phases'] = max(r['phases'], e.get('phases') or 0)
        r['calls'] += e.get('calls') or 1
        r['wire_dtype'] = e.get('wire_dtype') or r['wire_dtype']
    observed_us = {}
    for (op, _name, _instr), r in per_instr.items():
        row = observed_us.setdefault(
            op, {'us': 0.0, 'wire_bytes': 0, 'phases': 0, 'calls': 0,
                 'wire_dtype': None})
        row['us'] = round(row['us'] + sum(r['us']) / len(r['us']), 3)
        row['wire_bytes'] += r['wire_bytes']
        row['phases'] += r['phases']
        row['calls'] += r['calls']
        row['wire_dtype'] = r['wire_dtype'] or row['wire_dtype']
    collectives_cmp = None
    if collectives or collectives_predicted or observed_us:
        ops = set((collectives or {}).get('per_op', {})) | set(
            (collectives_predicted or {}).get('per_op', {})) | set(
            observed_us)
        collectives_cmp = {}
        for op in sorted(ops):
            obs = (collectives or {}).get('per_op', {}).get(op, {})
            pred = (collectives_predicted or {}).get(
                'per_op', {}).get(op, {})
            prof = observed_us.get(op, {})
            row = {
                'observed_calls': obs.get('calls'),
                'observed_bytes': obs.get('bytes'),
                'observed_us': prof.get('us'),
                'observed_wire_bytes': prof.get('wire_bytes') or None,
                'observed_phases': prof.get('phases') or None,
                'predicted_wire_bytes': pred.get('wire_bytes'),
                'predicted_est_us': pred.get('est_us'),
                'predicted_phases': pred.get('phases'),
                # the wire-dtype dimension: the compiled module's
                # payload element type (s8 under quantized
                # collectives) — prediction first, profiler join as
                # fallback, so the 2-4x byte claim is auditable per op
                'wire_dtype': (pred.get('wire_dtype')
                               or obs.get('wire_dtype')
                               or prof.get('wire_dtype')),
            }
            # the closed loop: profiled us over the cost-model
            # estimate, per op — what calibration is meant to pull
            # toward 1.0.  Both sides are per-step totals: the census
            # sums est_us over an op's call sites, and the profiler
            # emits each call site's per-execution us once.
            o_us, p_us = row['observed_us'], row['predicted_est_us']
            if o_us and p_us:
                row['us_ratio'] = round(o_us / p_us, 4)
            collectives_cmp[op] = row

    # -- auto-sharding plan: predicted-vs-actual for the chosen plan --
    plan = None
    plan_events = by_kind.get('plan_selected', [])
    if plan_events:
        last = plan_events[-1]
        plan = {
            'name': last.get('name'),
            'chips': last.get('chips'),
            'winner': last.get('winner'),
            'candidates_scored': last.get('candidates_scored'),
            'hbm_budget_bytes': last.get('hbm_budget_bytes'),
            'predicted_wire_bytes': last.get('wire_bytes'),
            'predicted_est_us': last.get('est_us'),
            'predicted_compute_us': last.get('compute_us'),
            'predicted_peak_bytes': last.get('peak_bytes'),
        }
        obs_bytes = (collectives or {}).get('total_bytes')
        plan['observed_bytes'] = obs_bytes
        obs_us = round(sum(r['us'] for r in observed_us.values()), 3) \
            if observed_us else None
        plan['observed_us'] = obs_us
        pred_us = plan.get('predicted_est_us')
        if obs_us and pred_us:
            plan['us_ratio'] = round(obs_us / pred_us, 4)

    # -- profile capture windows (telemetry.profile) --------------
    profile = None
    cap_events = by_kind.get('profile_capture', [])
    if cap_events:
        ok = [e for e in cap_events if not e.get('error')]
        last = (ok or cap_events)[-1]
        profile = {
            'windows': len(cap_events),
            'errors': len(cap_events) - len(ok),
            'collective_observed': sum(
                e.get('collective_observed') or 0 for e in cap_events),
            'last': {k: last.get(k) for k in (
                'name', 'step_lo', 'step_hi', 'device_us_per_step',
                'collective_us_per_step', 'collective_frac', 'trace',
                'error') if last.get(k) is not None},
        }

    # -- serving: the serve_step / serve_request join --------------
    # (emitted since PR 12, silently dropped until now)
    serving = None
    serve_steps = by_kind.get('serve_step', [])
    serve_reqs = by_kind.get('serve_request', [])
    serve_rejects = by_kind.get('serve_reject', [])
    fleet_events = by_kind.get('fleet_event', [])
    if serve_steps or serve_reqs or serve_rejects or fleet_events:
        ttft_ms = [r['ttft_s'] * 1000.0 for r in serve_reqs
                   if r.get('ttft_s') is not None]
        tpot_ms = [r['tpot_s'] * 1000.0 for r in serve_reqs
                   if r.get('tpot_s') is not None]
        # span tokens + carried prefill first tokens - preemption
        # rollbacks = the engine's delivered-token accounting
        decoded = sum((e.get('decoded') or 0)
                      + (e.get('prefilled') or 0)
                      - (e.get('discarded') or 0)
                      for e in serve_steps)
        ts = [e['ts'] for e in serve_steps if e.get('ts') is not None]
        wall = (max(ts) - min(ts)) if len(ts) > 1 else None
        by_cause = {}
        completed = evicted = 0
        for r in serve_reqs:
            cause = r.get('reason') or '?'
            by_cause[cause] = by_cause.get(cause, 0) + 1
            if r.get('state') == 'done':
                completed += 1
            else:
                evicted += 1
        requests_rows = [
            {k: r.get(k) for k in (
                'rid', 'state', 'reason', 'prompt_len', 'tokens',
                'ttft_s', 'tpot_s', 'preemptions', 'age_s', 'rank')
             if r.get(k) is not None}
            for r in serve_reqs]
        traces = {e['rid']: e.get('trace') or []
                  for e in by_kind.get('serve_trace', ())
                  if e.get('rid') is not None}
        serving = {
            'requests': len(serve_reqs),
            'completed': completed,
            'evicted': evicted,
            'by_cause': by_cause,
            'preemptions': sum(r.get('preemptions') or 0
                               for r in serve_reqs),
            'ttft_ms': _percentiles(ttft_ms),
            'tpot_ms': _percentiles(tpot_ms),
            'interventions': len(serve_steps),
            'decoded_tokens': decoded,
            'tokens_per_s': (round(decoded / wall, 3)
                             if wall else None),
            'last_step': {k: serve_steps[-1].get(k) for k in (
                'live', 'batch', 'span', 'queued', 'free_blocks',
                'total_blocks')} if serve_steps else None,
            'slo_breaches': [
                {k: e.get(k) for k in (
                    'what', 'observed_s', 'budget_s', 'observed_frac',
                    'threshold_frac', 'rank') if e.get(k) is not None}
                for e in by_kind.get('slo_breach', ())],
            'drift_detected': [
                {k: e.get(k) for k in (
                    'cause', 'op', 'instr', 'us_ratio', 'band',
                    'name', 'rank') if e.get(k) is not None}
                for e in by_kind.get('drift_detected', ())],
            'request_timeline': requests_rows,
            'traces': traces,
        }
        # admission shed trail (serve_reject): typed refusals are a
        # load signal, not an error — a front door that never sheds
        # under overload is one that OOMed instead
        if serve_rejects:
            shed_by_reason = {}
            for e in serve_rejects:
                reason = e.get('reason') or '?'
                shed_by_reason[reason] = \
                    shed_by_reason.get(reason, 0) + 1
            serving['rejected'] = len(serve_rejects)
            serving['shed_by_reason'] = shed_by_reason
        # router control plane (fleet_event): dispatch retries,
        # drains, warm-spare promotions, replica deaths — the fleet's
        # failure-handling story lines up against the request rows
        if fleet_events:
            by_action = {}
            for e in fleet_events:
                action = e.get('action') or '?'
                by_action[action] = by_action.get(action, 0) + 1
            serving['fleet'] = {
                'events': len(fleet_events),
                'by_action': by_action,
                'timeline': [
                    {k: e.get(k) for k in (
                        'action', 'replica', 'rid', 'cause',
                        'offset', 'rank') if e.get(k) is not None}
                    for e in fleet_events],
            }

    # -- memory: predicted vs compiled vs live ---------------------
    # One row per compiled module (newest memory_compiled wins — a
    # retrace replaces its module's row, same as the live registry),
    # joined with the sampler's live stream.  The predicted/compiled
    # ratio is the memory analogue of collectives_cmp's us_ratio: the
    # number calibration is meant to pull toward 1.0 so the planner's
    # HBM gate stops lying.
    memory = None
    mem_compiled = by_kind.get('memory_compiled', [])
    mem_samples = by_kind.get('memory_sample', [])
    if mem_compiled or mem_samples:
        modules = {}
        for e in mem_compiled:
            modules[e.get('name', '?')] = {
                k: e.get(k) for k in (
                    'source', 'predicted_peak_bytes',
                    'compiled_peak_bytes', 'argument_bytes',
                    'output_bytes', 'temp_bytes', 'alias_bytes',
                    'code_bytes', 'ratio')
                if e.get(k) is not None}
        ratios = [row['ratio'] for row in modules.values()
                  if row.get('ratio') is not None]
        live = None
        if mem_samples:
            last = mem_samples[-1]
            live = {k: last.get(k) for k in (
                'source', 'device_bytes', 'device_peak_bytes',
                'device_limit_bytes', 'host_rss', 'budget_bytes')
                if last.get(k) is not None}
            live['samples'] = len(mem_samples)
            peaks = [s.get('device_bytes') for s in mem_samples
                     if s.get('device_bytes') is not None]
            if peaks:
                live['max_device_bytes'] = max(peaks)
        memory = {
            'modules': modules,
            'live': live,
            'ratio_mean': (round(sum(ratios) / len(ratios), 4)
                           if ratios else None),
            'pressure_events': len(by_kind.get('memory_pressure', ())),
        }

    # -- lint findings -------------------------------------------
    lint = {}
    for e in by_kind.get('lint_finding', ()):
        lint[e.get('severity', '?')] = \
            lint.get(e.get('severity', '?'), 0) + 1

    # -- resilience timeline -------------------------------------
    timeline = []
    t0 = events[0]['ts'] if events else 0
    for e in events:
        is_res_span = (e['kind'] == 'span'
                       and e.get('name') in RESILIENCE_SPAN_NAMES)
        if e['kind'] not in RESILIENCE_KINDS and not is_res_span:
            continue
        kind = f"span:{e['name']}" if is_res_span else e['kind']
        row = {'t_rel_s': round((e.get('ts') or t0) - t0, 3),
               'kind': kind, 'rank': e.get('rank', 0)}
        for k in ('step', 'signum', 'strikes', 'rollbacks', 'path',
                  'moved_to', 'dur_s', 'dispatch_s', 'error',
                  'fault', 'seed', 'host', 'hosts', 'attempt',
                  'delay_s', 'mesh', 'saved_mesh',
                  'op', 'tag', 'budget_s', 'elapsed_s', 'missing',
                  'peer', 'heartbeat_age_s', 'live', 'stale',
                  'reason', 'deadline_s', 'clamped_from_s',
                  'what', 'cause', 'rid', 'observed_s', 'us_ratio',
                  'instr', 'observed_frac',
                  'skew', 'behind', 'hb_stale', 'spread', 'band',
                  'world', 'max_step', 'requested', 'fits',
                  'suspect',
                  'trigger', 'policy', 'outcome', 'stage',
                  'triggers', 'kinds', 'from_mesh', 'to_mesh',
                  'assignment', 'candidate_s', 'incumbent_s',
                  'margin', 'seq', 'ranks', 'site', 'sites',
                  'observed_bytes', 'peak_bytes', 'budget_bytes',
                  'watermark', 'frac', 'source', 'hbm_budget_gb'):
            if e.get(k) is not None:
                row[k] = e[k]
        timeline.append(row)

    # -- watchdog / collective supervision summary ----------------
    watchdog = None
    wd_kinds = ('timeout', 'straggler', 'quorum_lost',
                'coordinated_abort')
    if any(by_kind.get(k) for k in wd_kinds):
        watchdog = {}
        for k in wd_kinds:
            rows = by_kind.get(k, [])
            if not rows:
                continue
            per_rank = {}
            for e in rows:
                r = e.get('rank', 0)
                per_rank[r] = per_rank.get(r, 0) + 1
            watchdog[k] = {'count': len(rows), 'per_rank': per_rank}
        faults = by_kind.get('fault_injected', [])
        if faults:
            per_rank = {}
            for e in faults:
                r = e.get('rank', 0)
                per_rank[r] = per_rank.get(r, 0) + 1
            watchdog['fault_injected'] = {'count': len(faults),
                                          'per_rank': per_rank}

    # -- cluster: per-rank step skew + straggler attribution -------
    # Per-rank step-time stats (the tag-keyed section above blends
    # ranks — fine for one host, blind for a cluster).  With >= 2
    # stepping ranks, compute each rank's skew vs the cluster median
    # p50 and join the live plane's straggler_suspect /
    # rank_divergence edges.
    rank_steps = {}
    for ev in by_kind.get('steps', ()):
        r = ev.get('rank', 0)
        st = rank_steps.setdefault(
            r, {'times_ms': [], 'n': 0, 'last_step': None,
                'tags': set()})
        st['n'] += ev.get('n', 0)
        st['tags'].add(ev.get('tag', 'train'))
        st['times_ms'] += [t for t in ev.get('step_time_ms') or []
                           if t is not None]
        hi = ev.get('step_hi')
        if hi is not None:
            st['last_step'] = (hi if st['last_step'] is None
                               else max(st['last_step'], hi))
    cluster = None
    if len(rank_steps) >= 2:
        per_rank = {}
        p50s = []
        for r, st in sorted(rank_steps.items()):
            pct = _percentiles(st['times_ms'])
            row = {'steps': st['n'],
                   'last_step': st['last_step'],
                   'tags': sorted(st['tags'])}
            row.update({k: pct.get(k) for k in
                        ('mean_ms', 'p50_ms', 'p99_ms') if pct})
            per_rank[r] = row
            if pct.get('p50_ms'):
                p50s.append(pct['p50_ms'])
        med = _median(p50s)
        max_step = max((st['last_step'] for st in rank_steps.values()
                        if st['last_step'] is not None), default=None)
        worst = None
        for r, row in per_rank.items():
            if med and row.get('p50_ms'):
                row['skew'] = round(row['p50_ms'] / med, 4)
                if worst is None or row['skew'] > \
                        per_rank[worst]['skew']:
                    worst = r
            if max_step is not None and row.get('last_step') is not None:
                row['behind'] = max_step - row['last_step']
        cluster = {
            'ranks': {str(r): row for r, row in per_rank.items()},
            'max_step': max_step,
            'median_p50_ms': med,
            'straggler': ({'rank': worst,
                           'skew': per_rank[worst]['skew']}
                          if worst is not None
                          and per_rank[worst].get('skew', 0) >= 1.5
                          else None),
            'suspects': [
                {k: e.get(k) for k in (
                    'suspect', 'cause', 'skew', 'behind', 'hb_stale',
                    'max_step') if e.get(k) is not None}
                for e in by_kind.get('straggler_suspect', ())],
            'divergence': [
                {k: e.get(k) for k in (
                    'spread', 'band', 'per_rank', 'max_step')
                 if e.get(k) is not None}
                for e in by_kind.get('rank_divergence', ())],
        }

    ranks = sorted({e.get('rank', 0) for e in events})
    spans = {}
    for e in by_kind.get('span', ()):
        row = spans.setdefault(e.get('name', '?'),
                               {'count': 0, 'total_s': 0.0})
        row['count'] += 1
        row['total_s'] = round(row['total_s'] + (e.get('dur_s') or 0.0),
                               6)
    return {
        'schema_version': SCHEMA_VERSION,
        'hosts': ranks,
        'n_events': len(events),
        'sources': sources,
        'steps': steps_out,
        'total_steps': total_steps,
        'split': split,
        'compile': compile_out,
        'compile_cache': compile_cache,
        'retraces': retrace_out,
        'collectives': collectives,
        'collectives_predicted': collectives_predicted,
        'collectives_cmp': collectives_cmp,
        'plan': plan,
        'profile': profile,
        'serving': serving,
        'memory': memory,
        'clock_skew': skew or {},
        'cluster': cluster,
        'watchdog': watchdog,
        'lint_findings': lint,
        'spans': spans,
        'scalars_last': scalars_last,
        'timeline': timeline,
    }


def render(report, stream=None):
    out = stream or sys.stdout
    p = lambda *a: print(*a, file=out)      # noqa: E731
    p('================ paddle_tpu run report ================')
    p(f"hosts: {report['hosts']}   events: {report['n_events']}   "
      f"sources: {len(report['sources'])}")
    if report['steps']:
        p('\n-- step times --')
        for tag, st in report['steps'].items():
            if not st.get('steps'):
                p(f'  [{tag}] {st.get("count", 0)} steps (no timings)')
                continue
            p(f'  [{tag}] n={st["count"]}  mean={st["mean_ms"]:.2f}ms  '
              f'p50={st["p50_ms"]:.2f}  p90={st["p90_ms"]:.2f}  '
              f'p99={st["p99_ms"]:.2f}  max={st["max_ms"]:.2f}')
            sp = report['split'].get(tag)
            if sp:
                p(f'        device-step {sp["device_step_ms"]:.1f}ms '
                  f'vs host-wait {sp["host_wait_ms"]:.1f}ms '
                  f'({sp["host_wait_frac"]:.1%} waiting)')
    c = report['compile']
    p(f'\n-- compile --\n  {c["count"]} compiles, '
      f'{c["total_s"]:.2f}s total')
    for name, row in sorted(c['per_name'].items()):
        p(f'    {name}: {row["count"]}x {row["total_s"]:.2f}s')
    r = report['retraces']
    p(f'  retraces: {r["count"]}'
      + (f' (worst: {r.get("worst")} at {r.get("max_variants")} '
         'variants)' if r['count'] else ''))
    cc = report.get('compile_cache')
    if cc:
        rate = (f'{cc["hit_rate"]:.0%}' if cc.get('hit_rate') is not None
                else 'n/a')
        p(f'  cache: {cc["hits"]}/{cc["lookups"]} lookups hit ({rate}), '
          f'{cc["deserialized"]} deserialized, '
          f'{cc["serialized"]} serialized'
          + (f', {cc["quarantined"]} quarantined'
             if cc['quarantined'] else '')
          + (f', {cc["warm_start_entries"]} warm-start entries'
             if cc['warm_start_entries'] else ''))
        if cc.get('compile_time_saved_s'):
            p(f'  cache saved ~{cc["compile_time_saved_s"]:.2f}s of '
              'trace+lower')
        for name, row in sorted(cc['per_name'].items()):
            if name != '?':
                p(f'    {name}: {row["hits"]} hit / '
                  f'{row["misses"]} miss')
    if report['collectives'] or report.get('collectives_predicted'):
        co = report['collectives'] or report['collectives_predicted']
        p(f'\n-- collectives (mesh {co.get("mesh")}) --')
        cmp_rows = report.get('collectives_cmp') or {}
        p(f'    {"op":<20}{"observed":>22}{"predicted (cost model)":>28}')
        for op, row in sorted(cmp_rows.items()):
            if row.get('wire_dtype') and row['wire_dtype'] != 'f32':
                op = f'{op}[{row["wire_dtype"]}]'
            obs_parts = []
            if row['observed_calls'] is not None:
                obs_parts.append(f'{row["observed_calls"]}x '
                                 f'{row["observed_bytes"]:,} B')
            if row.get('observed_us') is not None:
                obs_parts.append(f'{row["observed_us"]:.0f} us')
                if row.get('us_ratio'):
                    obs_parts.append(f'(x{row["us_ratio"]:.2f})')
            obs = ' '.join(obs_parts) or '-'
            pred = '-'
            if row['predicted_wire_bytes'] is not None:
                pred = (f'{row["predicted_wire_bytes"]:,} B wire '
                        f'~{row["predicted_est_us"]:.0f} us')
            p(f'    {op:<20}{obs:>22}{pred:>28}')
        if report['collectives']:
            p(f'    observed total: '
              f'{report["collectives"]["total_bytes"]:,} bytes/step')
        if report.get('collectives_predicted'):
            cp = report['collectives_predicted']
            p(f'    predicted total: {cp["wire_bytes_total"]:,} wire '
              f'bytes/step, ~{cp["est_us_total"]:.0f} us on the wire')
    if report.get('plan'):
        pl = report['plan']
        w = pl.get('winner') or {}
        p('\n-- auto-sharding plan --')
        p(f'    {pl.get("name")}: winner {w.get("mesh")} '
          f'[{w.get("assignment")}]'
          + (f' +{w["fallback"]}' if w.get('fallback') else '')
          + f' of {pl.get("candidates_scored")} candidates')
        if pl.get('predicted_wire_bytes') is not None:
            p(f'    predicted: {pl["predicted_wire_bytes"]:,} wire '
              f'bytes/step, ~{pl.get("predicted_est_us", 0):.0f} us '
              'collectives, peak '
              f'{(pl.get("predicted_peak_bytes") or 0) / (1 << 30):.2f}'
              ' GiB')
        if pl.get('observed_bytes') is not None:
            obs_line = (f'    observed:  {pl["observed_bytes"]:,} '
                        'collective bytes/step')
            if pl.get('observed_us'):
                obs_line += f', {pl["observed_us"]:.0f} us'
                if pl.get('us_ratio'):
                    obs_line += f' (x{pl["us_ratio"]:.2f} of predicted)'
            p(obs_line)
    if report.get('profile'):
        pr = report['profile']
        last = pr.get('last') or {}
        p('\n-- profile captures --')
        p(f'    {pr["windows"]} window(s), '
          f'{pr["collective_observed"]} collective_observed event(s)'
          + (f', {pr["errors"]} failed' if pr.get('errors') else ''))
        if last.get('device_us_per_step') is not None:
            frac = last.get('collective_frac') or 0.0
            p(f'    last window [{last.get("name")}] steps '
              f'{last.get("step_lo")}-{last.get("step_hi")}: '
              f'{last["device_us_per_step"]:.0f} us/step device, '
              f'{last.get("collective_us_per_step", 0):.0f} us '
              f'({frac:.1%}) in collectives')
    if report.get('serving'):
        sv = report['serving']
        p('\n-- serving --')
        p(f'    {sv["requests"]} requests: {sv["completed"]} '
          f'completed, {sv["evicted"]} evicted '
          f'({", ".join(f"{c}:{n}" for c, n in sorted(sv["by_cause"].items()))})'
          + (f', {sv["preemptions"]} preemption(s)'
             if sv['preemptions'] else ''))
        tk = sv.get('tokens_per_s')
        p(f'    {sv["decoded_tokens"]} tokens over '
          f'{sv["interventions"]} interventions'
          + (f' ({tk:.0f} tokens/s)' if tk else ''))
        for label, pct in (('TTFT', sv['ttft_ms']),
                           ('TPOT', sv['tpot_ms'])):
            if pct:
                p(f'    {label}: p50={pct["p50_ms"]:.1f}ms '
                  f'p99={pct["p99_ms"]:.1f}ms '
                  f'max={pct["max_ms"]:.1f}ms (n={pct["steps"]})')
        last = sv.get('last_step')
        if last:
            p(f'    last intervention: {last.get("live")} live / '
              f'batch {last.get("batch")} / {last.get("queued")} '
              f'queued / {last.get("free_blocks")} of '
              f'{last.get("total_blocks")} blocks free')
        if sv.get('rejected'):
            sheds = ', '.join(
                f'{r}:{n}' for r, n in
                sorted(sv['shed_by_reason'].items()))
            p(f'    {sv["rejected"]} shed at admission ({sheds})')
        fleet = sv.get('fleet')
        if fleet:
            acts = ', '.join(f'{a}:{n}' for a, n in
                             sorted(fleet['by_action'].items()))
            p(f'    fleet: {fleet["events"]} control event(s) '
              f'({acts})')
            for e in fleet['timeline'][:8]:
                p(f'      {e.get("action")}: '
                  + ' '.join(f'{k}={e[k]}' for k in
                             ('replica', 'rid', 'cause', 'offset')
                             if e.get(k) is not None))
        for b in sv['slo_breaches']:
            p(f'    SLO BREACH: {b}')
        for d in sv['drift_detected']:
            p(f'    DRIFT: {d}')
        rows = sv['request_timeline']
        for r in rows[:8]:
            ttft = r.get('ttft_s')
            p(f'      {r.get("rid")}: {r.get("state")}'
              f'/{r.get("reason")} prompt={r.get("prompt_len")} '
              f'tokens={r.get("tokens")}'
              + (f' ttft={ttft * 1000:.0f}ms'
                 if ttft is not None else '')
              + (f' preempted x{r["preemptions"]}'
                 if r.get('preemptions') else ''))
        if len(rows) > 8:
            p(f'      ... {len(rows) - 8} more request(s) '
              '(--json has all)')
    if report.get('memory'):
        mem = report['memory']
        p('\n-- memory (predicted vs compiled vs live) --')
        mods = mem.get('modules') or {}
        if mods:
            p(f'    {"module":<26}{"predicted":>14}{"compiled":>14}'
              f'{"ratio":>8}')
            for name, row in sorted(mods.items()):
                pred = row.get('predicted_peak_bytes')
                comp = row.get('compiled_peak_bytes')
                ratio = row.get('ratio')
                p(f'    {name:<26}'
                  f'{(f"{pred:,} B" if pred is not None else "-"):>14}'
                  f'{(f"{comp:,} B" if comp is not None else "-"):>14}'
                  f'{(f"x{ratio:.2f}" if ratio is not None else "-"):>8}')
        if mem.get('ratio_mean') is not None:
            p(f'    mean predicted/compiled ratio: '
              f'x{mem["ratio_mean"]:.2f} (calibration pulls this '
              'toward 1.0)')
        live = mem.get('live')
        if live:
            bits = [f'{live["samples"]} sample(s) '
                    f'[{live.get("source", "?")}]']
            if live.get('device_bytes') is not None:
                bits.append(f'{live["device_bytes"]:,} B live')
            if live.get('max_device_bytes') is not None:
                bits.append(f'{live["max_device_bytes"]:,} B high-water')
            if live.get('host_rss') is not None:
                bits.append(f'rss {live["host_rss"]:,} B')
            if live.get('budget_bytes') is not None:
                bits.append(f'budget {live["budget_bytes"]:,} B')
            p(f'    live: {"  ".join(bits)}')
        if mem.get('pressure_events'):
            p(f'    MEMORY PRESSURE: {mem["pressure_events"]} '
              'event(s) (see resilience timeline)')
    if report.get('cluster'):
        cl = report['cluster']
        p('\n-- cluster (per-rank step skew) --')
        for r, row in sorted(cl['ranks'].items()):
            bits = [f'n={row.get("steps")}']
            if row.get('p50_ms') is not None:
                bits.append(f'p50={row["p50_ms"]:.2f}ms')
            if row.get('skew') is not None:
                bits.append(f'skew=x{row["skew"]:.2f}')
            if row.get('last_step') is not None:
                bits.append(f'step={row["last_step"]}')
            if row.get('behind'):
                bits.append(f'behind={row["behind"]}')
            p(f'    rank {r}: {"  ".join(bits)}')
        if cl.get('straggler'):
            s = cl['straggler']
            p(f'    straggler: rank {s["rank"]} at x{s["skew"]:.2f} '
              'the cluster median')
        for s in cl.get('suspects', ()):
            p(f'    SUSPECT (live): {s}')
        for d in cl.get('divergence', ()):
            p(f'    DIVERGENCE (live): {d}')
    if report.get('clock_skew'):
        p('\n-- clock skew (per-host anchor offsets applied) --')
        for r, off in sorted(report['clock_skew'].items()):
            p(f'    rank {r}: {off:+.3f}s')
    if report.get('watchdog'):
        p('\n-- watchdog / collective supervision --')
        for kind, row in sorted(report['watchdog'].items()):
            ranks = ', '.join(f'r{r}:{n}' for r, n in
                              sorted(row['per_rank'].items()))
            p(f'    {kind}: {row["count"]} ({ranks})')
    if report['lint_findings']:
        p(f'\n-- lint findings --\n    {report["lint_findings"]}')
    if report['scalars_last']:
        p('\n-- last scalars --')
        for tag, vals in report['scalars_last'].items():
            pretty = ', '.join(f'{k}={v:.5g}'
                               for k, v in sorted(vals.items()))
            p(f'    [{tag}] {pretty}')
    if report['timeline']:
        p('\n-- resilience timeline --')
        for row in report['timeline']:
            extra = {k: v for k, v in row.items()
                     if k not in ('t_rel_s', 'kind', 'rank')}
            p(f'  +{row["t_rel_s"]:9.3f}s r{row["rank"]} '
              f'{row["kind"]}' + (f'  {extra}' if extra else ''))
    else:
        p('\n-- resilience timeline --\n  (clean run: no events)')
    p('=======================================================')


def report_once(paths, as_json=False, stream=None):
    """One discover -> merge -> analyze -> render pass.  Returns the
    report dict, or None when nothing was found."""
    jsonls, flights = discover(paths)
    if not jsonls and not flights:
        return None
    events, sources, skew = load_events(jsonls, flights)
    report = analyze(events, sources, skew)
    out = stream or sys.stdout
    if as_json:
        print(json.dumps(report, indent=1, sort_keys=True), file=out)
    else:
        render(report, stream=out)
    return report


def follow(paths, interval_s=5.0, as_json=False, max_refreshes=None,
           stream=None, clear=None):
    """Live-tail mode: re-render the report from a RUNNING job's
    JSONL/flight-ring every `interval_s` seconds instead of waiting
    for job exit.  Safe against concurrent writers: the JSONL loader
    already skips a torn final line, and flight dumps are written
    atomically.  Stops on Ctrl-C (or after `max_refreshes` passes —
    tests/CI).  Returns the number of render passes."""
    out = stream or sys.stdout
    if clear is None:
        clear = out.isatty() and not as_json
    # status chatter goes to stdout only for the human renderer —
    # under --json stdout must stay a clean stream of report
    # documents (one per refresh), so stamps/waits route to stderr
    chat = sys.stderr if as_json else out
    n = 0
    try:
        while True:
            if clear:
                print('\x1b[2J\x1b[H', end='', file=out)
            report = report_once(paths, as_json=as_json, stream=out)
            if report is None:
                print(f'run_report --follow: waiting for telemetry '
                      f'under {paths} ...', file=chat)
            else:
                import datetime
                stamp = datetime.datetime.now().strftime('%H:%M:%S')
                print(f'[--follow {stamp}: {report["n_events"]} '
                      f'events, refresh every {interval_s:g}s, '
                      'Ctrl-C to stop]', file=chat)
            for s in {out, chat}:
                try:
                    s.flush()
                except (OSError, ValueError):
                    pass
            n += 1
            if max_refreshes is not None and n >= max_refreshes:
                return n
            time.sleep(interval_s)
    except KeyboardInterrupt:
        return n


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='run_report',
        description='Merge per-host telemetry JSONL (+ flight-recorder '
                    'dumps) into one run report.')
    ap.add_argument('paths', nargs='+',
                    help='telemetry dirs, telemetry-*.jsonl files, '
                         'and/or flightrec-*.json dumps')
    ap.add_argument('--json', action='store_true',
                    help='machine-readable report for bench/CI')
    ap.add_argument('--follow', action='store_true',
                    help='live-tail a RUNNING job: re-render every '
                         '--interval seconds instead of requiring '
                         'job exit (Ctrl-C to stop)')
    ap.add_argument('--interval', type=float, default=5.0,
                    help='refresh period for --follow (seconds, '
                         'default 5)')
    ap.add_argument('--refreshes', type=int, default=None,
                    help='with --follow: stop after N renders '
                         '(default: until Ctrl-C)')
    args = ap.parse_args(argv)

    if args.follow:
        follow(args.paths, interval_s=args.interval,
               as_json=args.json, max_refreshes=args.refreshes)
        return 0
    report = report_once(args.paths, as_json=args.json)
    if report is None:
        print('run_report: no telemetry-*.jsonl or flightrec-*.json '
              f'under {args.paths}', file=sys.stderr)
        return 2
    return 0


if __name__ == '__main__':
    sys.exit(main())
