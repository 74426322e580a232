"""analysis.spmd (SPMD contract lint) + the collective flight
recorder (distributed.collective ledger).

Static half: positive AND negative fixture per rule (rank-gated
collective, early-return gate, the broadcast post/fetch idiom, the
per-peer loop refinement, collective-order through branches and HLO
conditionals, host nondeterminism into payloads/traces with the
broadcast_object sanitizer, unbroadcast RNG seeding), the suppression
grammar, CLI --spmd exit codes + --json schema, and the tier-1
zero-HIGH self-lint gate over paddle_tpu/ + tools/.

Runtime half: CollectiveLedger ring/seq/frame units, diff_ledgers
window semantics (divergence, agreement, skew, incarnation reset),
probe_mismatch event emission, the CollectiveTimeout ledger-diff
enrichment (first mismatched entry + per-rank call sites in the
message), supervisor routing, and the 2-process ChaosCluster
end-to-end attribution of a seeded collective_skip (slow).

(File name sorts before test_host_embedding so the whole module runs
inside the tier-1 window.)
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import types

import numpy as np
import pytest

from paddle_tpu import analysis, telemetry
from paddle_tpu.analysis import hlo
from paddle_tpu.analysis.spmd import (
    lint_spmd_source, lint_spmd_file, lint_spmd_sources, SPMD_RULES)
from paddle_tpu.distributed.collective import (
    CollectiveLedger, CollectiveTimeout, FileKVStore, HostCollectives,
    LEDGER_ENV, LEDGER_KEY, diff_ledgers, get_ledger, ledger_enabled,
    probe_mismatch, reset_ledgers)
from paddle_tpu.telemetry.recorder import EVENT_KINDS, get_recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_state():
    """Virgin recorder + ledger registry per test — the per-process
    ledger singletons would otherwise leak seq streams across tests."""
    telemetry.disable()
    telemetry.reset()
    reset_ledgers()
    yield
    telemetry.disable()
    telemetry.reset()
    reset_ledgers()


def _lint(src, **kw):
    return lint_spmd_source(textwrap.dedent(src), **kw)


def _rules(findings, rule):
    return [f for f in findings if f.rule == rule]


# ================================== rule: rank-dependent-collective ========

RANK_GATED = """
    def sync(transport, rank, grads):
        if rank == 0:
            transport.allreduce(grads, 'mean', tag='g')
        return grads
"""

EARLY_RETURN = """
    def save(transport, grads):
        if transport.rank != 0:
            return None
        transport.barrier_host(tag='ckpt')
        return grads
"""

BROADCAST_IDIOM = """
    def bcast(transport, rank, src, payload, tag):
        if rank == src:
            transport.post(tag, 'bcast', payload)
        else:
            payload = transport.fetch(tag, src)
        return payload
"""

PEER_LOOP = """
    def exchange(self, tag, arr):
        self.post(tag, 'x', arr)
        out = {}
        for r in range(self.world):
            if r == self.rank:
                out[r] = arr
                continue
            out[r] = self.fetch(tag, r)
        return out
"""


class TestRankDependentCollective:
    def test_rank_gated_collective_is_high(self):
        # the static half of the PR's both-ways acceptance: the same
        # divergence class the runtime e2e seeds (a rank-gated skip)
        # must be flagged HIGH before the code ever runs
        fs = _rules(_lint(RANK_GATED), 'rank-dependent-collective')
        assert len(fs) == 1 and fs[0].severity == 'high'
        assert 'allreduce' in fs[0].message
        assert 'deadlock' in fs[0].message

    def test_early_return_gate_is_high(self):
        fs = _rules(_lint(EARLY_RETURN), 'rank-dependent-collective')
        assert len(fs) == 1 and fs[0].severity == 'high'
        assert 'barrier_host' in fs[0].message

    def test_broadcast_post_fetch_idiom_is_clean(self):
        # post/fetch are two roles of ONE logical collective: the
        # src/dst split must not be flagged
        assert not _lint(BROADCAST_IDIOM)

    def test_per_peer_loop_refinement_is_clean(self):
        # `for r in range(world): if r == self.rank` is the symmetric
        # iteration every rank runs identically — not a rank gate
        assert not _rules(_lint(PEER_LOOP),
                          'rank-dependent-collective')

    def test_env_rank_guard_is_high(self):
        fs = _rules(_lint("""
            import os

            def f(transport, x):
                if os.environ.get('PADDLE_TRAINER_ID') == '0':
                    transport.allgather(x, tag='t')
        """), 'rank-dependent-collective')
        assert len(fs) == 1 and fs[0].severity == 'high'

    def test_differing_sequences_both_sides_is_warn(self):
        fs = _rules(_lint("""
            def f(transport, rank, x):
                if rank == 0:
                    transport.allreduce(x, 'sum', tag='a')
                    transport.barrier_host(tag='b')
                else:
                    transport.allreduce(x, 'sum', tag='a')
        """), 'rank-dependent-collective')
        assert len(fs) == 1 and fs[0].severity == 'warn'


# ============================================ rule: collective-order =======

class TestCollectiveOrder:
    def test_differing_branches_warn(self):
        fs = _rules(_lint("""
            def f(transport, cfg, x):
                if cfg.fast:
                    transport.allreduce(x, 'sum', tag='a')
                else:
                    transport.allgather(x, tag='a')
        """), 'collective-order')
        assert len(fs) == 1 and fs[0].severity == 'warn'
        assert 'allreduce' in fs[0].message

    def test_identical_branches_clean(self):
        assert not _lint("""
            def f(transport, cfg, x):
                if cfg.fast:
                    transport.allreduce(x, 'sum', tag='a')
                else:
                    transport.allreduce(x, 'mean', tag='a')
        """)

    def test_rank_guard_owned_by_other_rule(self):
        # a rank predicate is the other rule's beat — no double report
        fs = _lint(RANK_GATED)
        assert not _rules(fs, 'collective-order')
        assert _rules(fs, 'rank-dependent-collective')


# ============================== rule: host-nondeterminism-into-trace =======

class TestHostNondeterminism:
    def test_time_into_payload_is_high(self):
        fs = _rules(_lint("""
            import time

            def f(transport):
                stamp = time.time()
                transport.allreduce(stamp, 'max', tag='t')
        """), 'host-nondeterminism-into-trace')
        assert len(fs) == 1 and fs[0].severity == 'high'
        assert 'time.time()' in fs[0].message

    def test_broadcast_object_sanitizes(self):
        assert not _lint("""
            import time

            def f(transport):
                stamp = time.time()
                stamp = transport.broadcast_object(stamp, src=0)
                transport.allreduce(stamp, 'max', tag='t')
        """)

    def test_trace_cast_is_warn(self):
        fs = _rules(_lint("""
            import os
            import jax.numpy as jnp

            def f():
                pid = os.getpid()
                return jnp.asarray(pid)
        """), 'host-nondeterminism-into-trace')
        assert len(fs) == 1 and fs[0].severity == 'warn'

    def test_set_iteration_taints(self):
        fs = _rules(_lint("""
            def f(transport, names):
                order = []
                for n in set(names):
                    order = order + [n]
                transport.allgather_object(order, tag='o')
        """), 'host-nondeterminism-into-trace')
        assert len(fs) == 1 and 'set(...)' in fs[0].message

    def test_stats_side_channel_is_not_a_sink(self):
        # post_stats is the non-blocking side channel, not a collective
        assert not _lint("""
            import time

            def f(transport):
                transport.post_stats({'ts': time.time()})
        """)


# ====================================== rule: unbroadcast-rng ==============

class TestUnbroadcastRng:
    def test_entropy_seeded_key_warns(self):
        fs = _rules(_lint("""
            import time
            from jax import random

            def f():
                seed = int(time.time())
                return random.PRNGKey(seed)
        """), 'unbroadcast-rng')
        assert len(fs) == 1 and fs[0].severity == 'warn'
        assert 'fold_in' in fs[0].message

    def test_broadcast_seed_is_clean(self):
        assert not _rules(_lint("""
            import time
            from jax import random

            def f(transport):
                seed = int(time.time())
                seed = transport.broadcast_object(seed, src=0)
                return random.PRNGKey(seed)
        """), 'unbroadcast-rng')


# ============================== HLO half: conditional collective-order =====

_HLO_ONE_SIDED = '\n'.join((
    'HloModule cond, num_partitions=2',
    '',
    '%add (a: f32[], b: f32[]) -> f32[] {',
    '  %a = f32[] parameter(0)',
    '  %b = f32[] parameter(1)',
    '  ROOT %s = f32[] add(%a, %b)',
    '}',
    '',
    '%true_b (p: f32[4]) -> f32[4] {',
    '  %p = f32[4]{0} parameter(0)',
    '  ROOT %ar = f32[4]{0} all-reduce(%p), replica_groups={{0,1}}, '
    'to_apply=%add',
    '}',
    '',
    '%false_b (q: f32[4]) -> f32[4] {',
    '  ROOT %q = f32[4]{0} parameter(0)',
    '}',
    '',
    'ENTRY %main (pred: pred[], x: f32[4]) -> f32[4] {',
    '  %pred = pred[] parameter(0)',
    '  %x = f32[4]{0} parameter(1)',
    '  ROOT %c = f32[4]{0} conditional(%pred, %x, %x), '
    'true_computation=%true_b, false_computation=%false_b',
    '}',
))


class TestHloCollectiveOrder:
    def test_one_sided_conditional_is_high(self):
        rep = hlo.audit_text(_HLO_ONE_SIDED)
        fs = [f for f in rep if f.rule == 'collective-order']
        assert len(fs) == 1 and fs[0].severity == 'high'
        assert fs[0].origin == 'hlo'
        assert 'all-reduce' in fs[0].message

    def test_matched_branches_are_clean(self):
        text = _HLO_ONE_SIDED.replace(
            'ROOT %q = f32[4]{0} parameter(0)',
            '%q2 = f32[4]{0} parameter(0)\n'
            '  ROOT %ar2 = f32[4]{0} all-reduce(%q2), '
            'replica_groups={{0,1}}, to_apply=%add')
        rep = hlo.audit_text(text)
        assert not [f for f in rep if f.rule == 'collective-order']


# ================================================ registry + sweep =========

class TestRegistryAndSweep:
    def test_four_rules_registered(self):
        assert set(SPMD_RULES) == {
            'rank-dependent-collective', 'collective-order',
            'host-nondeterminism-into-trace', 'unbroadcast-rng'}

    def test_disable_skips_rule(self):
        assert not _lint(RANK_GATED,
                         disable=('rank-dependent-collective',))

    def test_syntax_error_degrades_to_info(self):
        (f,) = _lint('def broken(:\n')
        assert f.rule == 'parse-error' and f.severity == 'info'

    def test_sweep_report_extras(self, tmp_path):
        (tmp_path / 'a.py').write_text(textwrap.dedent(RANK_GATED))
        (tmp_path / 'b.py').write_text('x = 1\n')
        rep = lint_spmd_sources([str(tmp_path)])
        assert rep.extras['spmd']['files'] == 2
        assert 'rank-dependent-collective' in \
            rep.extras['spmd']['rules']
        assert len(_rules(rep, 'rank-dependent-collective')) == 1

    def test_suppression_comment(self, tmp_path):
        p = tmp_path / 's.py'
        p.write_text(textwrap.dedent("""
            def sync(transport, rank, grads):
                if rank == 0:
                    transport.allreduce(grads, 'mean', tag='g')  # tpu-lint: disable=rank-dependent-collective
                return grads
        """))
        assert not lint_spmd_file(str(p))


# =============================================== tier-1 self-lint gate =====

class TestSelfLintGate:
    def test_repo_has_zero_high(self):
        rep = lint_spmd_sources([os.path.join(REPO, 'paddle_tpu'),
                                 os.path.join(REPO, 'tools')])
        high = [f for f in rep if f.severity == 'high']
        assert not high, analysis.LintReport(high).render(high)

    def test_repo_is_fully_clean(self):
        # the satellite sweep fixed or justified every finding (the
        # per-peer loop refinement in the rule, the replicated-config
        # suppression in quant_collectives) — keep it that way
        rep = lint_spmd_sources([os.path.join(REPO, 'paddle_tpu'),
                                 os.path.join(REPO, 'tools')])
        assert not len(rep), str(rep)


# ================================================================== CLI ====

def _cli(*args, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'tpu_lint.py'),
         *args], capture_output=True, text=True, env=env, cwd=cwd)


class TestCLI:
    def test_clean_file_exits_0(self, tmp_path):
        p = tmp_path / 'ok.py'
        p.write_text('x = 1\n')
        r = _cli(str(p), '--spmd')
        assert r.returncode == 0, r.stdout + r.stderr

    def test_high_finding_exits_1_and_json_schema(self, tmp_path):
        p = tmp_path / 'bad.py'
        p.write_text(textwrap.dedent(RANK_GATED))
        r = _cli(str(p), '--spmd', '--json')
        assert r.returncode == 1, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc['counts']['high'] == 1
        assert doc['extras']['spmd']['files'] == 1
        (f,) = [x for x in doc['findings']
                if x['rule'] == 'rank-dependent-collective']
        assert f['severity'] == 'high'
        assert f['file'] == str(p) and f['line']
        assert f['origin'] == 'ast'

    def test_spmd_without_paths_is_usage_error(self):
        r = _cli('--spmd')
        assert r.returncode == 2

    def test_fail_on_never_exits_0(self, tmp_path):
        p = tmp_path / 'bad.py'
        p.write_text(textwrap.dedent(RANK_GATED))
        r = _cli(str(p), '--spmd', '--fail-on', 'never')
        assert r.returncode == 0

    def test_self_lint_gate_cli(self):
        r = _cli('paddle_tpu/', 'tools/', '--spmd')
        assert r.returncode == 0, r.stdout + r.stderr


# ==================================================== collective ledger ====

class TestCollectiveLedger:
    def test_ring_bounds_and_monotone_seq(self):
        led = CollectiveLedger(0, depth=8)
        for i in range(20):
            led.record('allreduce-sum', f't{i}', shape=(4,),
                       dtype='float32')
        assert len(led) == 8 and led.seq == 20
        entries = led.entries()
        assert [e['seq'] for e in entries] == list(range(12, 20))
        e = entries[-1]
        assert e['op'] == 'allreduce-sum' and e['tag'] == 't19'
        assert e['shape'] == [4] and e['dtype'] == 'float32'
        assert e['site'] and ':' in e['site']

    def test_note_step_tags_entries(self):
        led = CollectiveLedger(0, depth=8)
        led.record('a', 't0')
        led.note_step(3)
        led.record('a', 't1')
        steps = [e['step'] for e in led.entries()]
        assert steps == [None, 3]

    def test_frame_doc(self):
        led = CollectiveLedger(1, depth=8)
        led.record('barrier', 'b')
        fr = led.frame()
        assert fr['rank'] == 1 and fr['seq'] == 1
        assert fr['depth'] == 8 and len(fr['entries']) == 1

    def test_get_ledger_singleton_and_reset(self):
        assert get_ledger(0) is get_ledger(0)
        assert get_ledger(0) is not get_ledger(1)
        led = get_ledger(0)
        led.record('a', 't')
        reset_ledgers()
        assert len(get_ledger(0)) == 0

    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv(LEDGER_ENV, '0')
        assert not ledger_enabled()
        monkeypatch.setenv(LEDGER_ENV, '1')
        assert ledger_enabled()
        monkeypatch.delenv(LEDGER_ENV)
        assert ledger_enabled()     # default ON


def _frame(rank, ops, start_seq=0, step=None, depth=256):
    entries = [{'seq': start_seq + i, 'op': op, 'tag': tag,
                'shape': [], 'dtype': '', 'step': step,
                'site': f'r{rank}.py:{10 + i}'}
               for i, (op, tag) in enumerate(ops)]
    return {'rank': rank, 'seq': start_seq + len(ops),
            'depth': depth, 'step': step, 'entries': entries}


class TestDiffLedgers:
    def test_fewer_than_two_frames_is_none(self):
        assert diff_ledgers({}) is None
        assert diff_ledgers({0: _frame(0, [('a', 't')])}) is None

    def test_agreement(self):
        d = diff_ledgers({0: _frame(0, [('a', 't0'), ('b', 't1')]),
                          1: _frame(1, [('a', 't0'), ('b', 't1')])})
        assert d['agree'] and d['seqs'] == {0: 2, 1: 2}

    def test_first_divergence_named_with_sites(self):
        d = diff_ledgers({
            0: _frame(0, [('a', 't0'), ('b', 'X'), ('c', 't2')]),
            1: _frame(1, [('a', 't0'), ('b', 'Y'), ('c', 'Z')])})
        assert d['seq'] == 1 and d['ranks'] == [0, 1]
        assert d['sites'] == {0: 'r0.py:11', 1: 'r1.py:11'}

    def test_head_skew_is_not_divergence(self):
        # rank 1 simply hasn't issued seq 1 yet — normal lag
        d = diff_ledgers({0: _frame(0, [('a', 't0'), ('b', 't1')]),
                          1: _frame(1, [('a', 't0')])})
        assert d['agree']

    def test_incarnation_reset_no_false_mismatch(self):
        # a restarted rank's ring starts at seq 0 while the surviving
        # rank's ring covers a far window — no overlap, no verdict
        old = _frame(0, [('z', 'big')], start_seq=5000)
        fresh = _frame(1, [('a', 't0')])
        d = diff_ledgers({0: old, 1: fresh})
        assert d['agree']

    def test_rotated_window_skips_rank(self):
        # rank 0's ring rotated past seq 0; comparison starts where
        # both windows overlap
        r0 = _frame(0, [('b', 't1'), ('c', 't2')], start_seq=1)
        r1 = _frame(1, [('a', 't0'), ('b', 't1'), ('c', 'DIFF')])
        d = diff_ledgers({0: r0, 1: r1})
        assert d['seq'] == 2


class TestProbeMismatch:
    def test_emits_event_on_divergence(self):
        led = get_ledger(0)
        led.note_step(4)
        led.record('allreduce-mean', 'stepA', site='train.py:10')
        peer = _frame(1, [('allreduce-mean', 'stepB')], step=4)
        tr = types.SimpleNamespace(
            rank=0, read_all_stats=lambda key=None: {1: peer})
        diff = probe_mismatch(tr, trigger='unit')
        assert diff and not diff.get('agree') and diff['seq'] == 0
        (ev,) = telemetry.events('collective_mismatch')
        assert ev['trigger'] == 'unit' and ev['op'] == 'allreduce-mean'
        assert ev['step'] == 4 and ev['ranks'] == [0, 1]
        assert ev['sites']['0'] == 'train.py:10'

    def test_agreement_emits_nothing(self):
        led = get_ledger(0)
        led.record('a', 't0', site='x.py:1')
        peer = _frame(1, [('a', 't0')])
        tr = types.SimpleNamespace(
            rank=0, read_all_stats=lambda key=None: {1: peer})
        d = probe_mismatch(tr, trigger='unit')
        assert d['agree']
        assert not telemetry.events('collective_mismatch')

    def test_never_raises(self):
        tr = types.SimpleNamespace(
            rank=0,
            read_all_stats=lambda key=None: 1 / 0)
        assert probe_mismatch(tr, trigger='unit') is None


# ============================= CollectiveTimeout ledger enrichment =========

class TestTimeoutEnrichment:
    def test_timeout_carries_first_divergent_entry(self, tmp_path):
        """Two in-process ranks issue MISMATCHED collectives: both
        time out, and the raised CollectiveTimeout names the first
        ledger divergence (op, seq, per-rank call sites) instead of
        only the generic missing-peers line — the satellite-2 pin."""
        kv = FileKVStore(str(tmp_path / 'kv'))
        t0 = HostCollectives(client=kv, rank=0, world=2,
                             timeout_s=1.0)
        t1 = HostCollectives(client=kv, rank=1, world=2,
                             timeout_s=1.0)
        t0.note_step(7)
        t1.note_step(7)
        errs = {}

        def run(r, t, tag):
            try:
                t.allreduce(np.ones(2), 'sum', tag=tag)
            except Exception as e:     # noqa: BLE001 - expected
                errs[r] = e

        ts = [threading.Thread(target=run, args=(0, t0, 'stepA')),
              threading.Thread(target=run, args=(1, t1, 'stepB'))]
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=30)
        assert all(not th.is_alive() for th in ts)
        for r in (0, 1):
            e = errs[r]
            assert isinstance(e, CollectiveTimeout)
            assert e.ledger_diff and not e.ledger_diff.get('agree')
            assert e.ledger_diff['seq'] == 0
            assert e.ledger_diff['step'] == 7
            assert 'ledger divergence @seq 0' in str(e)
            assert 'r0=' in str(e) and 'r1=' in str(e)
        # attribution event lands BEFORE the generic timeout event
        evs = telemetry.events()
        kinds = [ev['kind'] for ev in evs
                 if ev['kind'] in ('collective_mismatch', 'timeout')]
        assert 'collective_mismatch' in kinds
        assert kinds.index('collective_mismatch') < \
            kinds.index('timeout')

    def test_matched_collective_records_and_agrees(self, tmp_path):
        kv = FileKVStore(str(tmp_path / 'kv'))
        t0 = HostCollectives(client=kv, rank=0, world=2,
                             timeout_s=10.0)
        t1 = HostCollectives(client=kv, rank=1, world=2,
                             timeout_s=10.0)
        res = {}

        def run(r, t):
            res[r] = t.allreduce(np.full(2, float(r + 1)), 'sum',
                                 tag='s1')

        ts = [threading.Thread(target=run, args=(r, t))
              for r, t in ((0, t0), (1, t1))]
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=30)
        np.testing.assert_allclose(res[0], np.full(2, 3.0))
        for t in (t0, t1):
            (entry,) = get_ledger(t.rank).entries()
            assert entry['op'] == 'allreduce-sum'
            assert entry['tag'] == 's1'
        # both rings were published over the stats side channel
        frames = dict(t0.read_all_stats(key=LEDGER_KEY))
        assert set(frames) >= {0, 1}
        assert not telemetry.events('collective_mismatch')

    def test_ledger_off_disarms_recording(self, tmp_path,
                                          monkeypatch):
        monkeypatch.setenv(LEDGER_ENV, '0')
        kv = FileKVStore(str(tmp_path / 'kv'))
        t0 = HostCollectives(client=kv, rank=0, world=1)
        t0.allreduce(np.ones(2), 'sum', tag='x')
        assert len(get_ledger(0)) == 0


# ======================================== trainer step-ledger hook =========

def _engine_stub():
    """A ParallelTrainer shell with only the ledger-latch state — the
    hook must not depend on any other trainer wiring."""
    from paddle_tpu.parallel.engine import ParallelTrainer
    stub = ParallelTrainer.__new__(ParallelTrainer)
    stub._step_ledger_init = False
    stub._step_ledger = None
    return stub


class TestEngineStepLedger:
    def test_note_ledger_step_records_sync_site(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TRAINER_ID', '0')
        stub = _engine_stub()
        stub._note_ledger_step(3)
        stub._note_ledger_step(4, k=4)
        entries = get_ledger(0).entries()
        assert [(e['op'], e['tag'], e['step']) for e in entries] == [
            ('shard_map_step', 'step3', 3),
            ('shard_map_chunk', 'step4..7', 4)]

    def test_ledger_off_is_noop(self, monkeypatch):
        monkeypatch.setenv(LEDGER_ENV, '0')
        stub = _engine_stub()
        stub._note_ledger_step(3)
        assert stub._step_ledger is None
        assert len(get_ledger(0)) == 0


    @staticmethod
    def _trainer_run(monkeypatch, ledger_on):
        """Six steps of a real trainer after its compile, under a
        device->host transfer guard: (losses, compile events, ledger
        entries)."""
        import jax
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.parallel import ParallelTrainer
        monkeypatch.setenv(LEDGER_ENV, '1' if ledger_on else '0')
        reset_ledgers()
        telemetry.reset()
        telemetry.enable(None, flush_interval=4)
        try:
            rs = np.random.RandomState(0)
            X = rs.randn(8, 16).astype('float32')
            Y = rs.randn(8, 4).astype('float32')
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                                nn.Linear(32, 4))
            opt = paddle.optimizer.Momentum(
                learning_rate=0.01, parameters=net.parameters())
            tr = ParallelTrainer(net, opt,
                                 lambda o, y: ((o - y) ** 2).mean())
            tr.step(X, Y)               # compile outside the guard
            with jax.transfer_guard_device_to_host('disallow'):
                losses = [tr.step(X, Y) for _ in range(6)]
            return ([float(np.asarray(v)) for v in losses],
                    len(telemetry.events('compile')),
                    len(get_ledger(0)))
        finally:
            telemetry.disable()

    def test_ledger_on_trainer_is_sync_free_and_unperturbed(
            self, monkeypatch):
        """Recording every step's sync site reads no device value,
        and changes neither the losses nor the compile count."""
        monkeypatch.setenv('PADDLE_TRAINER_ID', '0')
        on = self._trainer_run(monkeypatch, True)
        off = self._trainer_run(monkeypatch, False)
        assert on[2] >= 7 and off[2] == 0    # recorded / disarmed
        assert on[0] == off[0]
        assert on[1] == off[1]


# ============================================= supervisor + vocabulary =====

class TestRoutingAndVocabulary:
    def test_kind_declared_and_routed(self):
        assert 'collective_mismatch' in EVENT_KINDS
        from paddle_tpu.resilience.supervisor import TRIGGER_POLICIES
        assert TRIGGER_POLICIES['collective_mismatch'] == 'backoff'

    def test_run_report_renders_kind(self):
        sys.path.insert(0, os.path.join(REPO, 'tools'))
        try:
            import run_report
        finally:
            sys.path.pop(0)
        assert 'collective_mismatch' in run_report.RESILIENCE_KINDS

    def test_supervisor_backoff_never_touches_host(self):
        from paddle_tpu.resilience.supervisor import (
            PlanSupervisor, SupervisorConfig)

        class _Host:
            calls = []
        sup = PlanSupervisor(_Host(), SupervisorConfig(
            debounce_s=0.01, cooldown_s=0.0))
        sup._handle({'kind': 'collective_mismatch', 'seq': 3,
                     'op': 'allreduce-mean', 'ranks': [0, 1]})
        inc = sup.incidents[-1]
        assert inc['outcome'] == 'backoff'
        assert not _Host.calls
        rem = telemetry.events('remediation')
        assert rem and rem[-1]['outcome'] == 'backoff'


# ====================================== cluster e2e attribution (slow) =====

# slow: spins real worker interpreters; nothing automatic runs it.
@pytest.mark.slow
@pytest.mark.faultinject
class TestClusterE2EAttribution:
    @staticmethod
    def _spin(tmp_path, faults):
        """A 2-process soak under `faults`: (report, merged events)."""
        from paddle_tpu.resilience.chaos import (
            ChaosCluster, FaultPlan, load_run_events)
        rep = ChaosCluster(
            procs=2, plan=FaultPlan(seed=11, name='spmd-e2e',
                                    faults=faults),
            steps=10, workdir=str(tmp_path / 'cluster'), save_every=2,
            collective_timeout_s=8.0, watchdog='step=60,grace=2',
            deadline_s=150.0).run()
        assert rep['ok'], rep['violations']
        return rep, load_run_events(str(tmp_path / 'cluster'))

    def test_seeded_skip_is_attributed_to_call_site(self, tmp_path):
        """The runtime half of the both-ways acceptance: a seeded
        collective_skip on rank 1 must surface as a
        collective_mismatch naming the exact soak-loop allreduce call
        site, before the generic timeout escalation."""
        rep, evs = self._spin(tmp_path, [
            {'kind': 'collective_skip', 'at_step': 5, 'rank': 1,
             'count': 1}])
        assert [e['fault'] for e in rep['injected']] == \
            ['collective_skip']
        mm = [e for e in evs if e.get('kind') == 'collective_mismatch']
        assert mm, 'seeded skip produced no collective_mismatch'
        sites = {s for e in mm for s in (e.get('sites') or {}).values()
                 if s}
        with open(os.path.join(REPO, 'tools', 'soak_run.py')) as f:
            seeded = next(f'soak_run.py:{no}'
                          for no, line in enumerate(f, 1)
                          if "transport.allreduce(w, 'mean'" in line)
        assert seeded in sites, (seeded, sites)
        tmo = [e for e in evs if e.get('kind') == 'timeout']
        assert tmo and min(e['ts'] for e in mm) <= \
            min(e['ts'] for e in tmo)

    def test_clean_twin_emits_no_mismatch(self, tmp_path):
        """The same cluster shape with an empty plan: not one ghost
        collective_mismatch."""
        _rep, evs = self._spin(tmp_path, [])
        assert not [e for e in evs
                    if e.get('kind') == 'collective_mismatch']
