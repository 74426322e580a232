"""paddle_tpu.analysis.hlo — the lowered-HLO SPMD audit.

HLO text parsing on a real 8-device forced-mesh lowering, the ring
cost model, one positive+negative fixture per HLO rule — including
the regression that ``replicated-giant-hlo`` catches the INPUT-derived
replicated intermediate the jaxpr const-dataflow rule provably misses
— the compile-choke-point escalations (to_static / Model.prepare /
ParallelTrainer under an active Mesh), the ``collective_cost``
telemetry join consumed by run_report's predicted-vs-observed table,
the multi-host clock-skew normalization, and the tier-1 HLO self-lint
gate over examples/ + paddle_tpu/models/.  (File name sorts before
test_host_embedding so the whole module runs inside the tier-1
window; conftest forces the 8-device CPU mesh.)
"""
import importlib.util
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis, nn
from paddle_tpu.analysis import costmodel, hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a 1 KiB bar keeps every fixture tiny while exercising the same code
# path the 64 MiB production threshold does
TINY = {'replicated_bytes': 1 << 10}


def dp_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ('dp',))


def rules_of(report, rule=None):
    if rule is None:
        return sorted({f.rule for f in report})
    return [f for f in report if f.rule == rule]


def shard(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def lowered_text(fn, mesh, in_shardings, *args):
    return jax.jit(fn, in_shardings=in_shardings).lower(
        *args).compile().as_text()


# ------------------------------------------------------------ cost model
class TestRingCostModel:
    def test_all_reduce_two_phase_ring(self):
        c = costmodel.ring_cost('all-reduce', 800, 8,
                                bw_gbps=100.0, latency_us=1.0)
        assert c['wire_bytes'] == 2 * 7 * 800 // 8
        assert c['phases'] == 14
        assert c['est_us'] == pytest.approx(
            14 * 1.0 + c['wire_bytes'] / (100.0 * 1e3), abs=1e-3)

    def test_all_gather_takes_gathered_size(self):
        c = costmodel.ring_cost('all-gather', 8000, 8)
        assert c['wire_bytes'] == 7 * 8000 // 8
        assert c['phases'] == 7

    def test_collective_permute_single_hop(self):
        c = costmodel.ring_cost('collective-permute', 4096, 8)
        assert c['wire_bytes'] == 4096 and c['phases'] == 1

    def test_group_of_one_and_unknown_op_cost_nothing(self):
        assert costmodel.ring_cost('all-reduce', 1 << 20, 1) == \
            {'wire_bytes': 0, 'phases': 0, 'est_us': 0.0}
        assert costmodel.ring_cost('transpose', 1 << 20, 8)[
            'wire_bytes'] == 0

    def test_latency_dominates_small_buffers(self):
        """EQuARX's motivating regime: a tiny all-reduce is latency-
        bound — the estimate must not collapse to ~0 with the bytes."""
        c = costmodel.ring_cost('all-reduce', 64, 8, latency_us=1.0)
        assert c['est_us'] >= 14


# ------------------------------------------------------- HLO text parsing
class TestHloParse:
    def test_buffer_bytes(self):
        assert hlo.buffer_bytes('f32[8,128]{1,0}') == 8 * 128 * 4
        assert hlo.buffer_bytes('bf16[16,16]{1,0}') == 16 * 16 * 2
        assert hlo.buffer_bytes('(f32[2]{0}, s32[]{:T(128)})') == 12
        assert hlo.buffer_bytes('pred[]') == 1

    def test_parse_real_lowered_module(self):
        mesh = dp_mesh()

        def step(x):
            return (x * x).sum()

        text = lowered_text(step, mesh, (shard(mesh, 'dp'),),
                            jax.ShapeDtypeStruct((64, 16), jnp.float32))
        mod = hlo.parse_module(text)
        assert mod.num_partitions == 8
        assert mod.entry is not None
        ops = {i.opcode for _, i in mod.walk()}
        assert 'parameter' in ops
        # the sum over the sharded dim partitions into an all-reduce
        census = hlo.collective_census(mod)
        assert census['all-reduce']['calls'] >= 1
        assert census['all-reduce']['group_size'] == 8
        assert census['all-reduce']['wire_bytes'] >= 1

    def test_census_group_size_follows_worst_call(self):
        """Multi-axis meshes mix group sizes under one base opcode
        (tp activation vs dp grad all-reduces): the census row's
        group_size must describe the call that set max_wire_bytes,
        not whichever call parsed first."""
        text = '\n'.join([
            'HloModule step, num_partitions=8',
            '',
            'ENTRY %main (p0: f32[256,256]) -> f32[256,256] {',
            '  %p0 = f32[256,256]{1,0} parameter(0)',
            '  %tiny = f32[8,8]{1,0} constant(0)',
            # group-of-2 all-reduce parses FIRST but moves few bytes
            '  %ar.tp = f32[8,8]{1,0} all-reduce(%tiny), '
            'replica_groups=[4,2]<=[8]',
            # group-of-4 all-reduce is the expensive one
            '  %ar.dp = f32[256,256]{1,0} all-reduce(%p0), '
            'replica_groups=[2,4]<=[8]',
            '  ROOT %out = f32[256,256]{1,0} add(%ar.dp, %ar.dp)',
            '}',
        ])
        census = hlo.collective_census(hlo.parse_module(text))
        row = census['all-reduce']
        assert row['calls'] == 2
        assert row['group_size'] == 4, row

    def test_instr_graph_operands_resolve(self):
        mesh = dp_mesh()

        def step(x):
            return jnp.tanh(x) + 1.0

        text = lowered_text(step, mesh, (shard(mesh, 'dp'),),
                            jax.ShapeDtypeStruct((8, 4), jnp.float32))
        mod = hlo.parse_module(text)
        for comp, ins in mod.walk():
            for op in ins.operands:
                # every operand name an instr references parses too
                # (fusions reference their params; index covers both)
                if op in comp.index:
                    assert comp.index[op].name == op

    def test_source_metadata_survives(self):
        mesh = dp_mesh()

        def step(x):
            return (x @ x.T).sum()

        text = lowered_text(step, mesh, (shard(mesh, 'dp'),),
                            jax.ShapeDtypeStruct((16, 16), jnp.float32))
        mod = hlo.parse_module(text)
        files = {i.file for _, i in mod.walk() if i.file}
        assert any(f.endswith('test_analysis_hlo.py') for f in files)


# ------------------------------------------- rule: replicated-giant-hlo
def _input_derived_giant(x):
    """The jaxpr false-negative fixture: z is derived ONLY from the
    input (no constants), the partitioner leaves it replicated at its
    full traced shape on every device."""
    y = x.sum(0)                    # all-reduce over the sharded dim
    z = jnp.outer(y, y)             # (128, 128) replicated everywhere
    return (x @ z).mean()


class TestReplicatedGiantHlo:
    X = jax.ShapeDtypeStruct((64, 128), jnp.float32)

    def test_regression_jaxpr_misses_hlo_catches(self):
        """THE closing-the-gap case: the jaxpr const-dataflow rule
        cannot flag an input-derived replicated intermediate; the
        post-partitioner buffer shape proves it."""
        mesh = dp_mesh()
        rj = analysis.lint(_input_derived_giant, self.X, mesh=mesh,
                           source=False, thresholds=TINY)
        assert rules_of(rj) == []               # jaxpr: blind to it
        rh = analysis.lint_hlo(_input_derived_giant, self.X, mesh=mesh,
                               thresholds=TINY)
        fs = rules_of(rh, 'replicated-giant-hlo')
        assert fs, rh.render()
        # verified against the re-traced global shapes -> HIGH
        assert fs[0].severity == 'high'
        assert fs[0].origin == 'hlo'

    def test_sharded_step_is_clean(self):
        mesh = dp_mesh()

        def step(x):
            return (x * 2.0).sum()

        rh = analysis.lint_hlo(step, self.X, mesh=mesh,
                               thresholds=TINY)
        assert not rules_of(rh, 'replicated-giant-hlo'), rh.render()

    def test_unverified_trace_degrades_to_warn(self):
        """audit_text with no global-shape join: replication cannot be
        proven, the finding degrades to WARN (advisory)."""
        mesh = dp_mesh()
        text = lowered_text(
            _input_derived_giant, mesh, (shard(mesh, 'dp'),), self.X)
        rh = hlo.audit_text(text, mesh=mesh, thresholds=TINY)
        fs = rules_of(rh, 'replicated-giant-hlo')
        assert fs and all(f.severity == 'warn' for f in fs)

    def test_disable_list_suppresses(self):
        mesh = dp_mesh()
        rh = analysis.lint_hlo(_input_derived_giant, self.X, mesh=mesh,
                               thresholds=TINY,
                               disable=('replicated-giant-hlo',))
        assert not rules_of(rh, 'replicated-giant-hlo')

    def test_shape_collision_with_bigger_global_degrades_to_warn(self):
        """A buffer whose dims tuple ALSO matches the per-device shard
        of a larger traced global (same dims with one axis scaled by a
        mesh factor) is ambiguous — it must not be a HIGH (the tier-1
        and bench gates fail on HIGH, so a collision would fail CI on
        a correctly sharded step)."""
        mesh = dp_mesh()
        text = lowered_text(
            _input_derived_giant, mesh, (shard(mesh, 'dp'),), self.X)
        # z is (128, 128); pretend the trace ALSO held a (1024, 128)
        # intermediate — (128, 128) is then equally its dp=8 shard
        rh = hlo.audit_text(text, mesh=mesh, thresholds=TINY,
                            global_shapes={(128, 128), (1024, 128)})
        fs = rules_of(rh, 'replicated-giant-hlo')
        assert fs, rh.render()
        assert all(f.severity == 'warn' for f in fs)
        assert 'shard of a larger traced' in fs[0].message
        # without the colliding shape the very same text is HIGH
        rh2 = hlo.audit_text(text, mesh=mesh, thresholds=TINY,
                             global_shapes={(128, 128)})
        fs2 = rules_of(rh2, 'replicated-giant-hlo')
        assert fs2 and fs2[0].severity == 'high'

    def test_maybe_local_shard_helper(self):
        gs = {(128, 128), (1024, 128), (64, 512)}
        assert hlo._maybe_local_shard((128, 128), gs, {'dp': 8}, 8)
        assert hlo._maybe_local_shard((64, 256), gs, {'tp': 2}, 2)
        # no mesh factor scales (128, 128) onto another global shape
        assert not hlo._maybe_local_shard((128, 128), gs, {'tp': 2}, 2)
        assert not hlo._maybe_local_shard((999, 7), gs, {'dp': 8}, 8)
        # 2D sharding: (32, 32) = dp x tp shard of a (64, 64) global
        gs2 = {(64, 64), (32, 32)}
        assert hlo._maybe_local_shard(
            (32, 32), gs2, {'dp': 2, 'tp': 2}, 4)
        # but not with only 2 devices: scaling both dims needs 4
        assert not hlo._maybe_local_shard((32, 32), gs2, {'dp': 2}, 2)

    def test_choke_point_shape_join_reuses_trace(self):
        """The escalation path: analysis.lint stashes the traced big
        shapes on its report; passing them to lint_hlo skips the
        second abstract trace and yields the same verified HIGH."""
        mesh = dp_mesh()
        rj = analysis.lint(_input_derived_giant, self.X, mesh=mesh,
                           source=False, thresholds=TINY)
        gs = rj.global_big_shapes
        assert (128, 128) in gs
        rh = analysis.lint_hlo(_input_derived_giant, self.X, mesh=mesh,
                               thresholds=TINY, global_shapes=gs)
        fs = rules_of(rh, 'replicated-giant-hlo')
        assert fs and fs[0].severity == 'high'

    def test_big_shape_walk_is_lazy(self, monkeypatch):
        """The single-device dev loop never escalates, so lint() must
        not pay the big-shape jaxpr walk until someone reads it."""
        calls = []
        real = hlo.global_big_shapes_of
        monkeypatch.setattr(
            hlo, 'global_big_shapes_of',
            lambda *a, **kw: calls.append(1) or real(*a, **kw))
        rj = analysis.lint(_input_derived_giant, self.X,
                           source=False, thresholds=TINY)
        assert calls == []                       # not computed eagerly
        gs = rj.global_big_shapes
        assert calls == [1] and (128, 128) in gs
        assert rj.global_big_shapes is gs        # cached, one walk
        assert calls == [1]


# ------------------------------------------------ rule: collective-cost
class TestCollectiveCost:
    X = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def test_oversized_collective_flagged(self):
        mesh = dp_mesh()

        def step(x):
            return (x * x).sum(0)

        rh = analysis.lint_hlo(
            step, self.X, mesh=mesh,
            thresholds={'collective_wire_warn': 1,
                        'collective_wire_high': 1 << 40})
        fs = rules_of(rh, 'collective-cost')
        assert fs and fs[0].severity == 'warn'
        assert 'wire' in fs[0].message

    def test_escalates_to_high_above_high_bar(self):
        mesh = dp_mesh()

        def step(x):
            return (x * x).sum(0)

        rh = analysis.lint_hlo(
            step, self.X, mesh=mesh,
            thresholds={'collective_wire_warn': 1,
                        'collective_wire_high': 1})
        fs = rules_of(rh, 'collective-cost')
        assert fs and fs[0].severity == 'high'

    def test_all_gather_feeding_elementwise_only(self):
        mesh = dp_mesh()

        def step(x):
            g = jax.lax.with_sharding_constraint(x, shard(mesh))
            return g * 3.0

        rh = analysis.lint_hlo(step, self.X, mesh=mesh,
                               in_shardings=(shard(mesh, 'dp'),))
        fs = [f for f in rules_of(rh, 'collective-cost')
              if 'elementwise' in f.message]
        assert fs, rh.render()

    def test_default_thresholds_quiet_on_small_step(self):
        mesh = dp_mesh()

        def step(x):
            return (x * x).sum()

        rh = analysis.lint_hlo(step, self.X, mesh=mesh)
        assert not rules_of(rh, 'collective-cost'), rh.render()

    def test_census_lands_in_extras(self):
        mesh = dp_mesh()

        def step(x):
            return (x * x).sum()

        rh = analysis.lint_hlo(step, self.X, mesh=mesh)
        ex = rh.extras
        assert ex['n_partitions'] == 8
        assert ex['collectives']['all-reduce']['calls'] >= 1
        assert ex['collective_wire_bytes'] >= 1
        assert ex['collective_est_us'] > 0
        # extras survive the JSON round trip tools consume
        assert json.loads(rh.to_json())['extras'][
            'n_partitions'] == 8


# ----------------------------------------------------- rule: resharding
class TestResharding:
    X = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def test_conflicting_constraints_force_all_to_all(self):
        mesh = dp_mesh()

        def step(x):
            a = jax.lax.with_sharding_constraint(
                x * 2.0, shard(mesh, 'dp', None))
            b = jax.lax.with_sharding_constraint(
                a + 1.0, shard(mesh, None, 'dp'))
            return b.sum()

        rh = analysis.lint_hlo(step, self.X, mesh=mesh,
                               in_shardings=(shard(mesh, 'dp', None),))
        fs = rules_of(rh, 'resharding')
        assert fs, rh.render()
        assert 'all-to-all' in fs[0].message

    def test_aligned_shardings_are_clean(self):
        mesh = dp_mesh()

        def step(x):
            a = jax.lax.with_sharding_constraint(
                x * 2.0, shard(mesh, 'dp', None))
            return a.sum()

        rh = analysis.lint_hlo(step, self.X, mesh=mesh,
                               in_shardings=(shard(mesh, 'dp', None),))
        assert not rules_of(rh, 'resharding'), rh.render()


# ---------------------------------------------------- rule: peak-memory
class TestPeakMemory:
    X = jax.ShapeDtypeStruct((64, 128), jnp.float32)

    def _step(self, x):
        return (jnp.tanh(x) @ x.T).sum()

    def test_estimate_is_positive_and_in_extras(self):
        mesh = dp_mesh()
        rh = analysis.lint_hlo(self._step, self.X, mesh=mesh)
        assert rh.extras['peak_bytes'] > 0
        assert rh.extras['hbm_budget_bytes'] == \
            hlo.DEFAULT_HLO_THRESHOLDS['hbm_bytes']
        assert not rules_of(rh, 'peak-memory')   # tiny step, 16G budget

    def test_over_budget_is_high(self):
        mesh = dp_mesh()
        rh = analysis.lint_hlo(self._step, self.X, mesh=mesh,
                               thresholds={'hbm_bytes': 64})
        fs = rules_of(rh, 'peak-memory')
        assert fs and fs[0].severity == 'high'
        assert 'OOM' in fs[0].message

    def test_zero_budget_flags_without_crashing(self):
        """--hbm-gb 0 is the strictest legitimate gate: every step is
        over budget; the finding must not divide by the zero budget."""
        mesh = dp_mesh()
        rh = analysis.lint_hlo(self._step, self.X, mesh=mesh,
                               thresholds={'hbm_bytes': 0})
        fs = rules_of(rh, 'peak-memory')
        assert fs and fs[0].severity == 'high'
        assert '%' not in fs[0].message

    def test_headroom_band_is_warn(self):
        mesh = dp_mesh()
        peak = analysis.lint_hlo(
            self._step, self.X, mesh=mesh).extras['peak_bytes']
        rh = analysis.lint_hlo(
            self._step, self.X, mesh=mesh,
            thresholds={'hbm_bytes': int(peak / 0.9)})  # 90% full
        fs = rules_of(rh, 'peak-memory')
        assert fs and fs[0].severity == 'warn'

    def test_liveness_walk_matches_hand_module(self):
        """A hand-written scheduled module: peak = params + both live
        temporaries before t0 dies (t1's last use frees it)."""
        text = '\n'.join((
            'HloModule hand, is_scheduled=true, num_partitions=2',
            '',
            'ENTRY %main (p0: f32[256]) -> f32[256] {',
            '  %p0 = f32[256]{0} parameter(0)',
            '  %t0 = f32[256]{0} add(%p0, %p0)',
            '  %t1 = f32[256]{0} multiply(%t0, %p0)',
            '  ROOT %t2 = f32[256]{0} subtract(%t1, %t0)',
            '}',
        ))
        mod = hlo.parse_module(text)
        # p0 (1 KiB) + t0 + t1 + t2 all live at the root: 4 KiB
        assert hlo.peak_memory(mod) == 4 * 1024


# ------------------------------------- compile choke-point escalations
class _Recorder:
    def __init__(self):
        self.calls = []
        self._real = analysis.lint_hlo

    def __call__(self, fn, *a, **kw):
        report = self._real(fn, *a, **kw)
        self.calls.append((kw.get('name'), report))
        return report


class TestChokePointEscalation:
    def _net(self):
        paddle.seed(0)
        return nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                             nn.Linear(8, 2))

    def test_parallel_trainer_escalates_under_mesh(self, monkeypatch):
        from paddle_tpu.parallel import ParallelTrainer
        rec = _Recorder()
        monkeypatch.setattr(analysis, 'lint_hlo', rec)
        net = self._net()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        tr = ParallelTrainer(
            net, opt, lambda out, y: nn.CrossEntropyLoss()(out, y),
            mesh=dp_mesh(), lint='error')
        x = np.random.RandomState(0).randn(8, 4).astype('float32')
        y = np.random.RandomState(1).randint(0, 2, (8, 1)).astype('int64')
        loss = tr.step(x, y)
        assert np.isfinite(float(np.asarray(loss)))
        # the escalation ran, with the REAL jit shardings, and the
        # trainer's own step survives its own audit at error level
        names = [n for n, _ in rec.calls]
        assert 'ParallelTrainer.step' in names
        rep = dict(rec.calls)['ParallelTrainer.step']
        assert rep.extras['n_partitions'] == 8
        assert not rep.high

    def test_model_prepare_escalates_under_mesh(self, monkeypatch):
        from paddle_tpu.distributed import env as denv
        rec = _Recorder()
        monkeypatch.setattr(analysis, 'lint_hlo', rec)
        prev = denv.get_mesh()
        denv.set_mesh(dp_mesh())
        try:
            net = self._net()
            m = paddle.Model(net)
            m.prepare(paddle.optimizer.Adam(
                learning_rate=0.1, parameters=net.parameters()),
                nn.CrossEntropyLoss(), lint='error')
            x = np.random.RandomState(0).randn(8, 4).astype('float32')
            y = np.random.RandomState(1).randint(
                0, 2, (8, 1)).astype('int64')
            loss, _ = m.train_batch([x], [y])
            assert np.isfinite(float(np.asarray(loss)))
        finally:
            denv.set_mesh(prev)
        assert 'Model.train_step' in [n for n, _ in rec.calls]
        rep = dict(rec.calls)['Model.train_step']
        assert rep.extras['n_partitions'] == 8
        assert not rep.high

    def test_no_mesh_no_escalation(self, monkeypatch):
        rec = _Recorder()
        monkeypatch.setattr(analysis, 'lint_hlo', rec)
        net = self._net()
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.Adam(
            learning_rate=0.1, parameters=net.parameters()),
            nn.CrossEntropyLoss(), lint='warn')
        x = np.random.RandomState(0).randn(8, 4).astype('float32')
        y = np.random.RandomState(1).randint(
            0, 2, (8, 1)).astype('int64')
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            m.train_batch([x], [y])
        assert rec.calls == []

    def test_to_static_check_escalates_under_mesh(self, monkeypatch):
        from paddle_tpu.distributed import env as denv
        rec = _Recorder()
        monkeypatch.setattr(analysis, 'lint_hlo', rec)
        prev = denv.get_mesh()
        denv.set_mesh(dp_mesh())
        try:
            net = self._net()
            fn = paddle.jit.to_static(net, check='warn')
            x = jnp.ones((8, 4), jnp.float32)
            with warnings.catch_warnings():
                warnings.simplefilter('ignore')
                fn(x)
        finally:
            denv.set_mesh(prev)
        assert len(rec.calls) == 1
        assert rec.calls[0][1].extras['n_partitions'] == 8


# ------------------------- telemetry join: predicted vs observed table
class TestCollectiveCostTelemetry:
    def _run_trainer(self, d):
        from paddle_tpu import telemetry
        from paddle_tpu.parallel import ParallelTrainer
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                            nn.Linear(8, 2))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        telemetry.enable(d)
        try:
            tr = ParallelTrainer(
                net, opt,
                lambda out, y: nn.CrossEntropyLoss()(out, y),
                mesh=dp_mesh(), lint=None)
            x = np.random.RandomState(0).randn(8, 4).astype('float32')
            y = np.random.RandomState(1).randint(
                0, 2, (8, 1)).astype('int64')
            tr.step(x, y)
        finally:
            telemetry.disable()

    def test_collective_cost_event_emitted(self, tmp_path):
        d = str(tmp_path)
        self._run_trainer(d)
        events = []
        for f in os.listdir(d):
            if f.startswith('telemetry-') and f.endswith('.jsonl'):
                with open(os.path.join(d, f)) as fh:
                    events += [json.loads(l) for l in fh if l.strip()]
        cost = [e for e in events if e.get('kind') == 'collective_cost']
        obs = [e for e in events if e.get('kind') == 'collectives']
        assert cost and obs
        assert cost[0]['wire_bytes_total'] >= 1
        assert cost[0]['est_us_total'] > 0
        # predicted and observed census agree on which ops exist —
        # both came from the same compiled module
        assert set(cost[0]['per_op']) == set(obs[0]['per_op'])
        for row in cost[0]['per_op'].values():
            assert set(row) >= {'calls', 'wire_bytes', 'est_us',
                                'group_size'}

    def test_run_report_joins_predicted_vs_observed(self, tmp_path):
        d = str(tmp_path)
        self._run_trainer(d)
        p = subprocess.run(
            [sys.executable,
             os.path.join(REPO, 'tools', 'run_report.py'), d,
             '--json'],
            capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        rep = json.loads(p.stdout)
        pred = rep['collectives_predicted']
        assert pred and pred['wire_bytes_total'] >= 1
        cmp_rows = rep['collectives_cmp']
        assert cmp_rows
        for op, row in cmp_rows.items():
            assert row['observed_calls'] >= 1
            assert row['predicted_wire_bytes'] >= 0
        # the human render shows the side-by-side table
        p2 = subprocess.run(
            [sys.executable,
             os.path.join(REPO, 'tools', 'run_report.py'), d],
            capture_output=True, text=True, timeout=120)
        assert 'predicted (cost model)' in p2.stdout
        assert 'predicted total' in p2.stdout


# --------------------------------- run_report: clock-skew normalization
def _load_run_report():
    spec = importlib.util.spec_from_file_location(
        'run_report', os.path.join(REPO, 'tools', 'run_report.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestClockSkewNormalization:
    def test_anchors_each_host_to_first_steps_event(self):
        rr = _load_run_report()
        events = [
            {'kind': 'steps', 'ts': 100.0, 'rank': 0},
            {'kind': 'checkpoint_save', 'ts': 101.0, 'rank': 0},
            # rank 1's wall clock runs 50 s ahead; its preemption
            # really happened BEFORE rank 0's checkpoint
            {'kind': 'steps', 'ts': 150.0, 'rank': 1},
            {'kind': 'preemption', 'ts': 150.5, 'rank': 1},
        ]
        skew = rr.normalize_clock_skew(events)
        assert skew == {0: 0.0, 1: 50.0}
        by = {(e['kind'], e['rank']): e['ts'] for e in events}
        assert by[('preemption', 1)] == pytest.approx(100.5)
        assert by[('preemption', 1)] < by[('checkpoint_save', 0)]

    def test_skipped_when_a_rank_never_stepped(self):
        rr = _load_run_report()
        events = [
            {'kind': 'steps', 'ts': 100.0, 'rank': 0},
            {'kind': 'preemption', 'ts': 150.5, 'rank': 1},
        ]
        assert rr.normalize_clock_skew(events) == {}
        assert events[1]['ts'] == 150.5        # untouched

    def test_single_host_is_noop(self):
        rr = _load_run_report()
        events = [{'kind': 'steps', 'ts': 100.0, 'rank': 0},
                  {'kind': 'preemption', 'ts': 101.0, 'rank': 0}]
        assert rr.normalize_clock_skew(events) == {}

    def test_merged_timeline_orders_and_reports_offsets(self, tmp_path):
        """End to end: two skewed JSONL streams merge into one
        correctly-ordered resilience timeline + a clock_skew section."""
        r0 = tmp_path / 'telemetry-0.jsonl'
        r1 = tmp_path / 'telemetry-1.jsonl'
        r0.write_text('\n'.join(json.dumps(e) for e in (
            {'kind': 'steps', 'ts': 100.0, 't': 1.0, 'rank': 0,
             'count': 4},
            {'kind': 'checkpoint_save', 'ts': 101.0, 't': 2.0,
             'rank': 0, 'step': 4},
        )) + '\n')
        r1.write_text('\n'.join(json.dumps(e) for e in (
            {'kind': 'steps', 'ts': 150.0, 't': 1.0, 'rank': 1,
             'count': 4},
            {'kind': 'preemption', 'ts': 150.5, 't': 1.5, 'rank': 1,
             'signum': 15},
        )) + '\n')
        p = subprocess.run(
            [sys.executable,
             os.path.join(REPO, 'tools', 'run_report.py'),
             str(tmp_path), '--json'],
            capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        rep = json.loads(p.stdout)
        assert rep['clock_skew'] == {'0': 0.0, '1': 50.0}
        kinds = [row['kind'] for row in rep['timeline']]
        assert kinds.index('preemption') < \
            kinds.index('checkpoint_save')


# ------------------------------------------------- CLI + tier-1 HLO gate
LINT_CLI = os.path.join(REPO, 'tools', 'tpu_lint.py')


def run_cli(*args, env_extra=None, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, LINT_CLI, *args], capture_output=True,
        text=True, env=env, cwd=REPO, timeout=timeout)


class TestCliHlo:
    def test_bad_mesh_spec_is_usage_error(self):
        res = run_cli('examples', '--hlo', '--mesh', 'dp8')
        assert res.returncode == 2
        assert 'axis=size' in res.stderr

    def test_jaxpr_target_hbm_gate(self, tmp_path):
        """--hlo on one --jaxpr callable: a micro HBM budget trips the
        peak-memory rule and the exit code gates on it."""
        mod = tmp_path / 'lintmod.py'
        mod.write_text(
            'import jax.numpy as jnp\n'
            'def step(x):\n'
            '    return (jnp.tanh(x) @ x.T).sum()\n')
        res = run_cli('--hlo', '--mesh', 'dp=8',
                      '--jaxpr', 'lintmod:step',
                      '--shapes', '64x128xf32',
                      '--hbm-gb', '0.00000001',
                      env_extra={'PYTHONPATH': str(tmp_path)})
        assert res.returncode == 1, res.stdout + res.stderr
        assert 'peak-memory' in res.stdout

    def test_hlo_crash_keeps_report_and_exits_2(self, tmp_path,
                                                monkeypatch, capsys):
        """A broken lower must not discard the AST/jaxpr report or
        silently disable the rest of the gate: the JSON still lands on
        stdout (a caller may parse stdout regardless of rc),
        hlo_error is recorded, and the exit code says infra-failure."""
        spec = importlib.util.spec_from_file_location(
            'tpu_lint_crash_t', LINT_CLI)
        tl = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tl)
        mod = tmp_path / 'lintmod_crash.py'
        mod.write_text('def step(x):\n    return (x * x).sum()\n')
        monkeypatch.syspath_prepend(str(tmp_path))

        def boom(*a, **k):
            raise RuntimeError('boom on hlo lower')

        monkeypatch.setattr(analysis, 'lint_hlo', boom)
        rc = tl.main(['--hlo', '--mesh', 'dp=8',
                      '--jaxpr', 'lintmod_crash:step',
                      '--shapes', '8x8xf32', '--json'])
        out = capsys.readouterr()
        assert rc == 2, out.out + out.err
        assert '--hlo audit failed' in out.err
        doc = json.loads(out.out)           # report survived the crash
        assert 'boom on hlo lower' in doc['hlo_error']
        assert 'counts' in doc

    def test_hlo_default_mesh_is_real_spmd(self, tmp_path):
        """--hlo with no --mesh must not silently audit a 1-device
        mesh: the default forces dp=8 virtual CPU devices so the
        partitioner actually partitions."""
        mod = tmp_path / 'lintmod_dflt.py'
        mod.write_text(
            'import jax.numpy as jnp\n'
            'def step(x):\n'
            '    return (x * x).sum()\n')
        res = run_cli('--hlo', '--jaxpr', 'lintmod_dflt:step',
                      '--shapes', '64x8xf32', '--json',
                      env_extra={'PYTHONPATH': str(tmp_path)})
        assert res.returncode == 0, res.stdout + res.stderr
        assert 'vacuous' not in res.stderr
        doc = json.loads(res.stdout)
        ex = doc['hlo']['lintmod_dflt:step']['extras']
        assert ex['n_partitions'] == 8, ex

    def test_mesh_build_failure_degrades_not_discards(self, tmp_path):
        """A backend that cannot satisfy the mesh (preset forced
        device count smaller than the axes product) must degrade to
        hlo_error with the report intact, not exit with no output."""
        mod = tmp_path / 'lintmod_nomesh.py'
        mod.write_text(
            'import jax.numpy as jnp\n'
            'def step(x):\n'
            '    return (x * x).sum()\n')
        res = run_cli('--hlo', '--mesh', 'dp=8',
                      '--jaxpr', 'lintmod_nomesh:step',
                      '--shapes', '8x8xf32', '--json',
                      env_extra={
                          'PYTHONPATH': str(tmp_path),
                          'XLA_FLAGS':
                              '--xla_force_host_platform_device_count=2'})
        assert res.returncode == 2, res.stdout + res.stderr
        assert 'audit skipped' in res.stderr
        doc = json.loads(res.stdout)        # report survived
        assert 'wants 8 devices' in doc['hlo_error']

    def test_one_device_mesh_warns_vacuous(self, tmp_path):
        """--hlo on a 1-device mesh partitions nothing: say so instead
        of emitting a clean 'SPMD audit' that never audited."""
        mod = tmp_path / 'lintmod_one.py'
        mod.write_text(
            'import jax.numpy as jnp\n'
            'def step(x):\n'
            '    return (x * x).sum()\n')
        res = run_cli('--hlo', '--mesh', 'dp=1',
                      '--jaxpr', 'lintmod_one:step',
                      '--shapes', '8x8xf32',
                      env_extra={'PYTHONPATH': str(tmp_path)})
        assert res.returncode == 0, res.stdout + res.stderr
        assert 'vacuous' in res.stderr

    def test_hlo_without_auditable_target_warns(self, tmp_path):
        """--hlo over a path that is neither examples/ nor models/
        (and no --jaxpr) must say it audited nothing rather than
        silently passing an 'SPMD audit' that never ran."""
        f = tmp_path / 'train.py'
        f.write_text('def loop():\n    return 1\n')
        res = run_cli(str(f), '--hlo', '--mesh', 'dp=8')
        assert res.returncode == 0
        assert 'nothing to audit' in res.stderr

    def test_scope_flag_documented_in_help(self):
        res = run_cli('--help')
        assert res.returncode == 0
        assert '--scope' in res.stdout
        assert '--hlo' in res.stdout
        assert '--mesh' in res.stdout
        assert '--hbm-gb' in res.stdout


class TestSelfLintHlo:
    """The tier-1 HLO gate: examples/ + paddle_tpu/models/ lower
    through the SPMD partitioner under the forced 8-device mesh with
    ZERO high-severity HLO findings (the acceptance bar)."""

    def test_cli_hlo_gate_examples_and_models(self):
        res = run_cli(os.path.join(REPO, 'examples'),
                      os.path.join(REPO, 'paddle_tpu', 'models'),
                      '--hlo', '--mesh', 'dp=8', '--json',
                      '--fail-on', 'never')
        assert res.returncode == 0, res.stdout + res.stderr
        doc = json.loads(res.stdout)
        assert doc['counts']['high'] == 0, doc
        # gptserve joined the suite in PR 12 (the serving decode step
        # as an audit target)
        assert set(doc['hlo']) == {'gpt', 'widedeep', 'lenet',
                                   'gptserve'}
        for name, rep in doc['hlo'].items():
            assert rep['counts']['high'] == 0, (name, rep)
            ex = rep['extras']
            assert ex['n_partitions'] == 8
            assert ex['peak_bytes'] > 0
            # every audited model trains data-parallel: its grad
            # all-reduce must appear in the census with a cost
            assert ex['collectives']['all-reduce']['est_us'] > 0

    def test_host_loop_sweep_runs_clean(self):
        """The --scope all satellite: the tools/ + tests/ step-loop
        sweep gates at zero high (host-audit demotion keeps boundary
        readbacks advisory)."""
        res = run_cli(os.path.join(REPO, 'tools'),
                      os.path.join(REPO, 'tests'), '--scope', 'all')
        assert res.returncode == 0, res.stdout[-2000:] + res.stderr
