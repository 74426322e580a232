"""paddle_tpu.analysis.planner — the auto-sharding planner, and the
topology-aware cost model it closes the loop with.

Pins: the corrected torus formulas (multi-axis all-reduce phase
counts, all-to-all store-and-forward bytes) against the flat-ring
model they replace; mesh/assignment enumeration; scoring monotonicity
(more chips on the dominant axis never ranks worse once compute
dominates — stated knobs); the HBM-budget fallback to remat /
half-batch plans; the shared --plan/--hlo lowering memo; the
``tpu_lint --plan`` CLI JSON schema; ``ParallelTrainer(auto_shard=
True)`` applying the winner + emitting ``plan_selected``; the
run_report predicted-vs-actual plan join; and the
calibrate_costmodel alpha/beta fit round-trip.  (File name sorts
before test_host_embedding so the whole module runs inside the
tier-1 window; conftest forces the 8-device CPU mesh.)
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.analysis import costmodel, hlo, planner, targets

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    path = os.path.join(REPO, 'tools', f'{name}.py')
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_mlp():
    paddle.seed(0)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                         nn.Linear(32, 4))


def tp_model():
    """Two Linears, the first with declared tp specs."""
    paddle.seed(0)
    l1, l2 = nn.Linear(16, 32), nn.Linear(32, 4)
    l1._param_shardings = {'weight': (None, 'tp'), 'bias': ('tp',)}
    return nn.Sequential(l1, l2)


def batch_sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# --------------------------------------------------- torus cost model
class TestTorusCostModel:
    def test_multi_axis_all_reduce_phase_count(self):
        """THE flat-ring fix: an all-reduce spanning a 4x2 torus pays
        per-axis ring phases (2*(3+1)=8), not one 8-ring (14) — the
        wire bytes are unchanged (they must still leave the chip)."""
        s = 1600
        torus = costmodel.torus_cost('all-reduce', s,
                                     (('dp', 4), ('tp', 2)))
        ring = costmodel.ring_cost('all-reduce', s, 8)
        assert torus['wire_bytes'] == ring['wire_bytes'] \
            == 2 * 7 * s // 8
        assert torus['phases'] == 8
        assert ring['phases'] == 14
        assert torus['est_us'] < ring['est_us']

    def test_three_axis_all_reduce(self):
        t = costmodel.torus_cost('all-reduce', 800, (2, 2, 2))
        assert t['phases'] == 2 * (1 + 1 + 1)
        assert t['wire_bytes'] == 2 * 7 * 800 // 8

    def test_all_to_all_store_and_forward(self):
        """Torus all-to-all forwards the full buffer fraction along
        EACH axis: more bytes than the flat ring's (n-1)/n bound, in
        far fewer phases."""
        s = 800
        t = costmodel.torus_cost('all-to-all', s, (4, 2))
        assert t['phases'] == 3 + 1
        assert t['wire_bytes'] == int(s * 3 / 4 + s * 1 / 2)
        ring = costmodel.ring_cost('all-to-all', s, 8)
        assert ring['phases'] == 7
        assert ring['wire_bytes'] == 7 * s // 8
        assert t['wire_bytes'] > ring['wire_bytes']

    def test_all_gather_multi_axis_keeps_ring_bytes(self):
        # per-axis gathers move (n-1)/n of the gathered size total
        s = 8000
        t = costmodel.torus_cost('all-gather', s, (4, 2))
        assert t['phases'] == 3 + 1
        assert t['wire_bytes'] == pytest.approx(7 * s // 8, abs=8)

    def test_reduce_scatter_multi_axis(self):
        s = 8000
        t = costmodel.torus_cost('reduce-scatter', s, (4, 2))
        assert t['phases'] == 3 + 1
        assert t['wire_bytes'] == pytest.approx(7 * s // 8, abs=8)

    def test_single_axis_is_byte_exact_ring(self):
        for op in costmodel.COLLECTIVE_OPS:
            a = costmodel.ring_cost(op, 12345, 8)
            b = costmodel.torus_cost(op, 12345, (8,))
            assert a['wire_bytes'] == b['wire_bytes'], op
            assert a['phases'] == b['phases'], op

    def test_axes_for_group_inference(self):
        mesh = {'dp': 4, 'tp': 2}
        assert costmodel.axes_for_group(mesh, 8) == \
            (('dp', 4), ('tp', 2))
        assert costmodel.axes_for_group(mesh, 4) == (('dp', 4),)
        assert costmodel.axes_for_group(mesh, 2) == (('tp', 2),)
        # a group that matches no axis subset degrades to a flat ring
        assert costmodel.axes_for_group(mesh, 3) == ((None, 3),)
        assert costmodel.axes_for_group(None, 8) == ((None, 8),)
        assert costmodel.axes_for_group(
            {'dp': 2, 'tp': 2, 'pp': 2}, 8) == \
            (('dp', 2), ('tp', 2), ('pp', 2))
        assert costmodel.axes_for_group(mesh, 1) == ()

    def test_axis_aware_bandwidth_and_latency(self):
        """A slow minor axis must show up in the estimate — the old
        flat ring priced every hop at one link's numbers."""
        fast = costmodel.torus_cost(
            'all-reduce', 1 << 20, (('dp', 4), ('tp', 2)),
            bw_gbps={'dp': 90.0, 'tp': 90.0})
        slow_tp = costmodel.torus_cost(
            'all-reduce', 1 << 20, (('dp', 4), ('tp', 2)),
            bw_gbps={'dp': 90.0, 'tp': 9.0})
        assert slow_tp['est_us'] > fast['est_us']
        lat = costmodel.torus_cost(
            'all-reduce', 64, (('dp', 4), ('tp', 2)),
            latency_us={'dp': 1.0, 'tp': 10.0, 'default': 1.0})
        assert lat['est_us'] >= 2 * 3 * 1.0 + 2 * 1 * 10.0

    def test_calibration_overrides_and_round_trip(self, tmp_path):
        cal = costmodel.Calibration(per_op={
            'all-reduce': {'alpha_us': 2.0, 'beta_us_per_byte': 1e-3}})
        t = costmodel.torus_cost('all-reduce', 1600, (4, 2),
                                 calibration=cal)
        assert t['est_us'] == pytest.approx(
            2.0 * t['phases'] + 1e-3 * t['wire_bytes'], abs=1e-2)
        path = os.path.join(tmp_path, 'cal.json')
        cal.save(path)
        back = costmodel.load_calibration(path)
        assert back.per_op == cal.per_op
        with pytest.raises(ValueError):
            costmodel.Calibration.from_dict({'version': 99})

    def test_calibration_link_knobs_reanchor_defaults(self):
        """A table with only measured link numbers (no fitted per-op
        alpha/beta) must still re-anchor the analytic defaults — in
        torus_cost AND through the census path — while an explicit
        non-default override keeps winning."""
        cal = costmodel.Calibration(link_bw_gbps=9.0)
        slow = costmodel.torus_cost('all-reduce', 1 << 20, (8,),
                                    calibration=cal)
        base = costmodel.torus_cost('all-reduce', 1 << 20, (8,))
        assert slow['est_us'] > base['est_us']
        explicit = costmodel.torus_cost('all-reduce', 1 << 20, (8,),
                                        bw_gbps=900.0,
                                        calibration=cal)
        assert explicit['est_us'] < base['est_us']
        text = """HloModule m, num_partitions=8

ENTRY %main (p0: f32[262144]) -> f32[262144] {
  %p0 = f32[262144]{0} parameter(0)
  ROOT %ar = f32[262144]{0} all-reduce(f32[262144]{0} %p0), replica_groups=[1,8]<=[8], to_apply=%sum
}
"""
        mod = hlo.parse_module(text)
        plain = hlo.collective_census(mod)
        anchored = hlo.collective_census(mod, calibration=cal)
        assert anchored['all-reduce']['est_us'] > \
            plain['all-reduce']['est_us']

    def test_census_decomposes_groups_on_the_mesh(self):
        """The regression the satellite names: a dp x tp mesh used to
        be costed as one flat ring over all chips."""
        text = """HloModule m, num_partitions=8

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p0), replica_groups=[1,8]<=[8], to_apply=%sum
}
"""
        mod = hlo.parse_module(text)
        flat = hlo.collective_census(mod)
        torus = hlo.collective_census(mod,
                                      mesh_shape={'dp': 4, 'tp': 2})
        assert flat['all-reduce']['phases'] == 14
        assert torus['all-reduce']['phases'] == 8
        assert torus['all-reduce']['wire_bytes'] == \
            flat['all-reduce']['wire_bytes']
        assert torus['all-reduce']['axes'] == (('dp', 4), ('tp', 2))
        assert torus['all-reduce']['est_us'] < \
            flat['all-reduce']['est_us']


# --------------------------------------------------- mesh enumeration
class TestEnumeration:
    def test_enumerate_meshes_8_chips(self):
        meshes = planner.enumerate_meshes(8, include_pp=False)
        got = {(m['dp'], m['tp']) for m in meshes}
        assert got == {(8, 1), (4, 2), (2, 4), (1, 8)}
        assert all('pp' not in m for m in meshes)

    def test_enumerate_meshes_includes_3d(self):
        meshes = planner.enumerate_meshes(8, include_pp=True)
        got = {(m['dp'], m['tp'], m['pp']) for m in meshes}
        assert (2, 2, 2) in got            # the 3D torus layout
        assert (8, 1, 1) in got and (1, 8, 1) in got
        assert (1, 1, 8) in got
        assert all(a * b * c == 8 for a, b, c in got)

    def test_enumerate_non_power_of_two(self):
        meshes = planner.enumerate_meshes(6, include_pp=False)
        got = {(m['dp'], m['tp']) for m in meshes}
        assert got == {(6, 1), (3, 2), (2, 3), (1, 6)}
        assert planner.enumerate_meshes(1, include_pp=False) == \
            [{'dp': 1, 'tp': 1}]

    def test_assignments_for(self):
        model = tp_model()
        # tp>1 mesh: declared specs bite; dp>1: fsdp variant exists
        a = planner.assignments_for(model, {'dp': 4, 'tp': 2})
        assert set(a) == {'declared', 'replicated', 'fsdp'}
        assert a['declared']['0.weight'] == (None, 'tp')
        # the fsdp variant dp-shards the param the specs left whole
        assert a['fsdp']['1.weight'] == ('dp', None)
        assert a['fsdp']['0.weight'] == (None, 'tp')
        # dp-only mesh: declared resolves to nothing -> dropped
        a = planner.assignments_for(model, {'dp': 8, 'tp': 1})
        assert 'declared' not in a and 'fsdp' in a
        # 1-device mesh: only replication remains
        a = planner.assignments_for(model, {'dp': 1, 'tp': 1})
        assert set(a) == {'replicated'}


# --------------------------------------------------- planner scoring
@pytest.fixture(scope='module')
def mlp_plan():
    model = small_mlp()
    return planner.plan_model(
        model, (batch_sds(16, 16), ), chips=8, include_pp=False,
        name='mlp')


class TestPlannerScoring:
    def test_ranks_many_candidates_without_executing(self, mlp_plan):
        assert len(mlp_plan.candidates) >= 6
        assert not mlp_plan.errors
        ranks = [p.rank for p in mlp_plan.candidates]
        assert ranks == list(range(1, len(ranks) + 1))
        # every candidate was actually scored from a lowered module
        for p in mlp_plan.candidates:
            assert p.scored_via == 'hlo'
            assert p.peak_bytes > 0
            assert p.score_us >= p.est_us >= 0

    def test_winner_fits_and_leads(self, mlp_plan):
        w = mlp_plan.winner
        assert w is not None and w.fits
        assert w is mlp_plan.candidates[0]
        scores = [p.score_us for p in mlp_plan.candidates if p.fits]
        assert scores == sorted(scores)

    def test_plan_json_and_event_shape(self, mlp_plan):
        doc = mlp_plan.to_json()
        assert doc['winner']['mesh'] == dict(
            mlp_plan.winner.mesh_axes)
        assert {'candidates', 'fallbacks', 'hbm_budget_bytes',
                'chips'} <= set(doc)
        ev = mlp_plan.to_event()
        assert ev['candidates_scored'] == len(mlp_plan.candidates)
        assert ev['winner']['assignment'] == \
            mlp_plan.winner.assignment
        assert ev['wire_bytes'] == mlp_plan.winner.wire_bytes

    def test_monotonic_in_dominant_axis_when_compute_bound(self):
        """More chips on the batch (dominant) axis never ranks worse
        once per-device compute dominates the estimate — pinned with
        explicit knobs (fast links + a slow chip) because at
        micro-model scale the latency term honestly dominates and
        SMALL meshes win."""
        model = small_mlp()
        res = planner.plan_model(
            model, (batch_sds(512, 16),), chips=8, include_pp=False,
            thresholds={'link_bw_gbps': 9000.0,
                        'link_latency_us': 0.01,
                        'peak_tflops': 0.001, 'hbm_gbps': 2.0},
            name='mlp-big')
        by_dp = {p.mesh_axes['dp']: p for p in res.candidates
                 if p.assignment == 'replicated'}
        assert {8, 4, 2, 1} <= set(by_dp)
        for hi, lo in ((8, 4), (4, 2)):
            assert by_dp[hi].score_us < by_dp[lo].score_us, (
                hi, lo, {d: p.score_us for d, p in by_dp.items()})
            assert by_dp[hi].rank < by_dp[lo].rank
        # dp=1 is NOT on the chain: with every input replicated GSPMD
        # is free to auto-shard internally (and does) — the guarantee
        # is only that dp=8 never ranks worse than it
        assert by_dp[8].score_us <= by_dp[1].score_us
        # and the compute floor is what drives the ordering: fewer
        # batch rows per device = less per-device work
        assert by_dp[8].compute_us < by_dp[4].compute_us \
            < by_dp[2].compute_us

    def test_hbm_budget_fallbacks(self):
        """When nothing fits the budget the planner must come back
        with explicit remat / half-batch plans, not an empty hand."""
        model = small_mlp()
        res = planner.plan_model(
            model, (batch_sds(16, 16),), chips=8, include_pp=False,
            hbm_budget_gb=1e-6, max_candidates=4, name='mlp-oom')
        assert res.candidates and not any(
            p.fits for p in res.candidates)
        kinds = {p.fallback for p in res.fallbacks}
        assert 'remat' in kinds and 'half-batch' in kinds
        for p in res.fallbacks:
            assert p.fallback in ('remat', 'half-batch')
            assert p.peak_bytes > 0
        half = [p for p in res.fallbacks
                if p.fallback == 'half-batch'][0]
        assert half.batch_scale == 0.5

    def test_zero_budget_flags_everything(self):
        model = small_mlp()
        res = planner.plan_model(
            model, (batch_sds(16, 16),), chips=8, include_pp=False,
            hbm_budget_gb=0, max_candidates=2, name='mlp-zero')
        assert res.candidates
        assert not any(p.fits for p in res.candidates)

    def test_pp_candidates_are_modeled_and_labeled(self):
        model = small_mlp()
        res = planner.plan_model(
            model, (batch_sds(16, 16),), chips=8, name='mlp-pp')
        pp = [p for p in res.candidates
              if p.mesh_axes.get('pp', 1) > 1]
        assert pp, 'include_pp=True must enumerate pipeline layouts'
        for p in pp:
            assert p.scored_via == 'pp-model'
            assert any('1F1B' in n or 'analytically' in n
                       for n in p.notes)

    def test_shared_lowering_cache(self):
        """One lowering per (target, mesh, shardings): a second plan
        over the same cache re-lowers nothing, and the --hlo audit
        path reuses the planner's compiled text for the matching
        triple (the tpu_lint --plan/--hlo ride-along fix)."""
        from paddle_tpu import analysis
        from paddle_tpu.distributed import env as _env
        from jax.sharding import NamedSharding, PartitionSpec as P
        cache = {}
        model = small_mlp()
        batch = (batch_sds(16, 16),)
        planner.plan_model(model, batch, chips=8, include_pp=False,
                           lower_cache=cache, name='mlp')
        n = len(cache)
        assert n >= 6
        planner.plan_model(model, batch, chips=8, include_pp=False,
                           lower_cache=cache, name='mlp')
        assert len(cache) == n, 'second plan must hit the memo'
        # the --hlo audit of the dp=8 declared posture = the planner's
        # dp=8 replicated candidate (same resolved shardings)
        mesh = planner._build_mesh(jax.devices(), {'dp': 8, 'tp': 1})
        prev = _env.get_mesh()
        _env.set_mesh(mesh)
        try:
            model2 = small_mlp()
            params, buffers, p_sh, b_sh = targets.target_state(
                model2, mesh)
            batch_sh = targets.batch_shardings(mesh, batch)
            ck = targets.cache_key('mlp', mesh.shape, p_sh, batch_sh,
                                   batch=batch)
            assert ck in cache, 'audit key must match the planner key'
            repl = NamedSharding(mesh, P())
            rep = analysis.lint_hlo(
                targets.surrogate_step(model2), params, buffers,
                jax.random.PRNGKey(0), *batch, mesh=mesh,
                in_shardings=(p_sh, b_sh, repl) + batch_sh,
                lower_cache=cache, cache_key=ck, name='hlo:mlp')
        finally:
            _env.set_mesh(prev)
        assert len(cache) == n, '--hlo must reuse the plan lowering'
        assert rep.extras.get('peak_bytes', 0) > 0

    def test_max_candidates_prunes_mesh_major(self):
        """Truncation keeps every assignment of the cheapest meshes
        (never drops whole assignment families) and is surfaced, not
        silent."""
        model = tp_model()
        res = planner.plan_model(
            model, (batch_sds(16, 16),), chips=8, include_pp=False,
            max_candidates=2, name='tp-capped')
        assert res.enumerated > 2
        assert len(res.candidates) == 2
        # the flat dp=8 mesh enumerates first: both its assignments
        # survive the cap (assignment-major ordering would have
        # scored 'declared' meshes only)
        assert all(p.mesh_axes == {'dp': 8, 'tp': 1}
                   for p in res.candidates)
        assert {p.assignment for p in res.candidates} == \
            {'replicated', 'fsdp'}
        assert 'scored 2 of' in res.render()
        assert res.to_json()['enumerated'] == res.enumerated

    def test_compute_floor_counts_custom_call_gemms(self):
        """Backends that lower matmuls to custom-calls must still
        price compute — the target name, not the type spec, carries
        the signal."""
        text = """HloModule m, num_partitions=1

ENTRY %main (p0: f32[128,64], p1: f32[64,32]) -> f32[128,32] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %p1 = f32[64,32]{1,0} parameter(1)
  ROOT %cc = f32[128,32]{1,0} custom-call(f32[128,64]{1,0} %p0, f32[64,32]{1,0} %p1), custom_call_target="__onednn$matmul"
}
"""
        mod = hlo.parse_module(text)
        us = planner.compute_floor_us(mod, peak_tflops=1e-6,
                                      hbm_gbps=1e12)
        assert us == pytest.approx(2 * 128 * 64 * 32, rel=1e-3)

    def test_compute_floor_math(self):
        """The FLOPs proxy is exact for a plain matmul
        (2·sqrt(|A|·|B|·|C|) = 2·m·k·n) and the floor takes the
        max of the flops and HBM-traffic terms."""
        text = """HloModule m, num_partitions=1

ENTRY %main (p0: f32[128,64], p1: f32[64,32]) -> f32[128,32] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %p1 = f32[64,32]{1,0} parameter(1)
  ROOT %d = f32[128,32]{1,0} dot(f32[128,64]{1,0} %p0, f32[64,32]{1,0} %p1)
}
"""
        mod = hlo.parse_module(text)
        # 1e-6 TFLOPs = 1 flop/us: the floor IS the flop count
        us = planner.compute_floor_us(mod, peak_tflops=1e-6,
                                      hbm_gbps=1e12)
        assert us == pytest.approx(2 * 128 * 64 * 32, rel=1e-3)
        # giant bandwidth + giant chip: traffic term takes over
        us2 = planner.compute_floor_us(mod, peak_tflops=1e9,
                                       hbm_gbps=1e-3)
        assert us2 == pytest.approx(128 * 32 * 4 / 1.0, rel=1e-3)


# ----------------------------------------------------------- CLI
class TestPlanCli:
    def test_plan_cli_json_schema(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        env['XLA_FLAGS'] = ' '.join(
            t for t in env.get('XLA_FLAGS', '').split()
            if not t.startswith(
                '--xla_force_host_platform_device_count'))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools', 'tpu_lint.py'),
             '--plan', '--chips', '8', '--targets', 'lenet',
             '--no-pp', '--max-candidates', '4', '--json'],
            capture_output=True, text=True, timeout=420, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        doc = json.loads(proc.stdout)
        assert 'plan' in doc and 'lenet' in doc['plan']
        res = doc['plan']['lenet']
        assert res['chips'] == 8
        assert len(res['candidates']) >= 2
        for row in res['candidates']:
            assert {'mesh', 'assignment', 'wire_bytes', 'est_us',
                    'compute_us', 'score_us', 'peak_bytes', 'fits',
                    'rank', 'scored_via', 'fallback'} <= set(row)
        assert res['winner'] == res['candidates'][0]
        assert 'plan_error' not in doc

    def test_plan_cli_rejects_unknown_target(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools', 'tpu_lint.py'),
             '--plan', '--chips', '8', '--targets', 'nope'],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2
        assert 'unknown --targets' in proc.stderr


# ------------------------------------------- trainer auto_shard
class TestTrainerAutoShard:
    @pytest.fixture(autouse=True)
    def _restore_env_mesh(self):
        """auto_shard takes ownership of the ambient mesh by design
        (_env.set_mesh on the winner); tests must not leak that into
        other modules."""
        from paddle_tpu.distributed import env as _env
        prev = _env.get_mesh()
        yield
        _env.set_mesh(prev)

    def test_auto_shard_plans_applies_and_emits(self, tmp_path):
        import paddle_tpu.optimizer as optim
        from paddle_tpu import telemetry
        from paddle_tpu.parallel.engine import ParallelTrainer
        tdir = os.path.join(tmp_path, 'tel')
        telemetry.enable(tdir)
        try:
            model = small_mlp()
            opt = optim.Adam(learning_rate=1e-3,
                             parameters=model.parameters())

            def loss_fn(out, y):
                return nn.functional.cross_entropy(out, y)

            tr = ParallelTrainer(
                model, opt, loss_fn,
                auto_shard={'max_candidates': 5, 'include_pp': False},
                hbm_budget_gb=16)
            assert tr.plan is None      # planning waits for shapes
            x = np.random.RandomState(0).randn(16, 16).astype(
                'float32')
            y = np.random.RandomState(1).randint(
                0, 4, (16,)).astype('int64')
            losses = [tr.loss_float(tr.step(x, y)) for _ in range(3)]
            assert all(np.isfinite(l) for l in losses)
            # the winner was applied: trainer mesh == plan mesh
            assert tr.plan is not None
            assert dict(tr.mesh.shape) == tr.plan.mesh_axes
            assert tr.param_specs == tr.plan.param_specs
        finally:
            telemetry.disable()
        evs = []
        for f in os.listdir(tdir):
            if not f.endswith('.jsonl'):
                continue
            for line in open(os.path.join(tdir, f)):
                rec = json.loads(line)
                if rec.get('kind') == 'plan_selected':
                    evs.append(rec)
        assert len(evs) == 1
        ev = evs[0]
        assert ev['winner']['mesh'] == {
            a: s for a, s in tr.plan.mesh_axes.items()}
        assert ev['candidates_scored'] >= 2
        assert ev['peak_bytes'] > 0

    def test_auto_shard_rejects_include_pp(self):
        """A pp>1 winner would run pp-way redundant compute with no
        1F1B schedule behind it — the trainer must refuse the
        override, not apply a pipeline-priced plan to a plain mesh."""
        import paddle_tpu.optimizer as optim
        from paddle_tpu.parallel.engine import ParallelTrainer
        model = small_mlp()
        opt = optim.Adam(learning_rate=1e-3,
                         parameters=model.parameters())

        def loss_fn(out, y):
            return nn.functional.cross_entropy(out, y)

        tr = ParallelTrainer(
            model, opt, loss_fn,
            auto_shard={'include_pp': True, 'max_candidates': 3})
        x = np.zeros((16, 16), 'float32')
        y = np.zeros((16,), 'int64')
        with pytest.warns(RuntimeWarning, match='include_pp'):
            tr.step(x, y)
        assert tr.plan is not None
        assert tr.plan.mesh_axes.get('pp', 1) == 1

    def test_auto_shard_budget_miss_degrades_with_warning(self):
        import paddle_tpu.optimizer as optim
        from paddle_tpu.parallel.engine import ParallelTrainer
        model = small_mlp()
        opt = optim.Adam(learning_rate=1e-3,
                         parameters=model.parameters())

        def loss_fn(out, y):
            return nn.functional.cross_entropy(out, y)

        tr = ParallelTrainer(
            model, opt, loss_fn,
            auto_shard={'max_candidates': 2, 'include_pp': False},
            hbm_budget_gb=0)
        x = np.zeros((16, 16), 'float32')
        y = np.zeros((16,), 'int64')
        with pytest.warns(RuntimeWarning, match='auto_shard'):
            tr.step(x, y)
        # it still trained (hand-specified posture) — just unplanned
        assert tr._step_no == 1


# ---------------------------------------- run_report plan join
class TestRunReportPlanJoin:
    def _events(self):
        return [
            {'kind': 'plan_selected', 'ts': 1.0, 'name': 'GPT',
             'chips': 8, 'candidates_scored': 12,
             'hbm_budget_bytes': 16 << 30,
             'winner': {'mesh': {'dp': 4, 'tp': 2},
                        'assignment': 'declared', 'fallback': None},
             'wire_bytes': 1 << 20, 'est_us': 120.0,
             'compute_us': 40.0, 'peak_bytes': 2 << 30},
            {'kind': 'collectives', 'ts': 2.0,
             'mesh': {'dp': 4, 'tp': 2},
             'per_op': {'all-reduce': {'calls': 3, 'bytes': 900000}},
             'total_bytes': 900000},
            {'kind': 'collective_cost', 'ts': 2.5,
             'mesh': {'dp': 4, 'tp': 2},
             'per_op': {'all-reduce': {'calls': 3,
                                       'wire_bytes': 1 << 20,
                                       'est_us': 120.0,
                                       'phases': 30,
                                       'group_size': 8}},
             'wire_bytes_total': 1 << 20, 'est_us_total': 120.0},
            {'kind': 'collective_observed', 'ts': 3.0,
             'op': 'all-reduce', 'wire_bytes': 900000, 'phases': 10,
             'us': 130.0},
        ]

    def test_plan_join_and_schema(self, tmp_path):
        rr = _load_tool('run_report')
        path = os.path.join(tmp_path, 'telemetry-r0.jsonl')
        with open(path, 'w') as f:
            for e in self._events():
                f.write(json.dumps(e) + '\n')
        events, sources, skew = rr.load_events([path], [])
        report = rr.analyze(events, sources, skew)
        assert report['schema_version'] == 1
        plan = report['plan']
        assert plan['winner']['mesh'] == {'dp': 4, 'tp': 2}
        assert plan['predicted_wire_bytes'] == 1 << 20
        assert plan['observed_bytes'] == 900000
        assert plan['observed_us'] == 130.0
        assert plan['us_ratio'] == pytest.approx(130.0 / 120.0,
                                                 abs=1e-3)
        cmp_row = report['collectives_cmp']['all-reduce']
        assert cmp_row['observed_us'] == 130.0
        assert cmp_row['predicted_phases'] == 30
        # no plan events -> key stays None (additive schema)
        report2 = rr.analyze(
            [e for e in self._events()
             if e['kind'] != 'plan_selected'], [], {})
        assert report2['plan'] is None

    def test_render_mentions_plan(self, tmp_path, capsys):
        rr = _load_tool('run_report')
        report = rr.analyze(self._events(), [], {})
        rr.render(report)
        out = capsys.readouterr().out
        assert 'auto-sharding plan' in out
        assert 'winner' in out


# ------------------------------------------- calibration fit
class TestCalibrate:
    def test_fit_recovers_alpha_beta(self, tmp_path):
        cc = _load_tool('calibrate_costmodel')
        rng = np.random.RandomState(0)
        path = os.path.join(tmp_path, 'telemetry-r0.jsonl')
        with open(path, 'w') as f:
            for i in range(40):
                wire = int(rng.choice([1 << 14, 1 << 18, 1 << 22]))
                phases = int(rng.choice([2, 6, 14, 30]))
                us = 2.5 * phases + 5e-4 * wire + rng.normal(0, 0.3)
                f.write(json.dumps(
                    {'kind': 'collective_observed', 'ts': float(i),
                     'op': 'all-reduce', 'wire_bytes': wire,
                     'phases': phases, 'us': round(us, 4)}) + '\n')
        out = os.path.join(tmp_path, 'cal.json')
        rc = cc.main([str(tmp_path), '-o', out])
        assert rc == 0
        cal = costmodel.load_calibration(out)
        row = cal.per_op['all-reduce']
        assert row['alpha_us'] == pytest.approx(2.5, abs=0.3)
        assert row['beta_us_per_byte'] == pytest.approx(5e-4,
                                                       rel=0.05)
        # the planner-side consumer: calibrated estimate beats default
        c = costmodel.torus_cost('all-reduce', 1 << 20, (4, 2),
                                 calibration=cal)
        assert c['est_us'] == pytest.approx(
            row['alpha_us'] * 8 + row['beta_us_per_byte']
            * c['wire_bytes'], rel=1e-3)

    def test_beta_only_fallback_on_singular_samples(self, tmp_path):
        cc = _load_tool('calibrate_costmodel')
        path = os.path.join(tmp_path, 'telemetry-r0.jsonl')
        with open(path, 'w') as f:
            for i in range(5):      # identical geometry every time
                f.write(json.dumps(
                    {'kind': 'collective_observed', 'ts': float(i),
                     'op': 'all-gather', 'wire_bytes': 1 << 20,
                     'phases': 7, 'us': 500.0}) + '\n')
        out = os.path.join(tmp_path, 'cal.json')
        assert cc.main([str(tmp_path), '-o', out]) == 0
        doc = json.load(open(out))
        row = doc['per_op']['all-gather']
        assert row['mode'] == 'beta-only'
        assert row['beta_us_per_byte'] >= 0

    def test_no_samples_is_an_error(self, tmp_path):
        cc = _load_tool('calibrate_costmodel')
        path = os.path.join(tmp_path, 'telemetry-r0.jsonl')
        with open(path, 'w') as f:
            f.write(json.dumps({'kind': 'steps', 'ts': 0.0}) + '\n')
        assert cc.main([str(tmp_path),
                        '-o', os.path.join(tmp_path, 'c.json')]) == 2

    def test_fit_from_run_report_doc(self, tmp_path):
        """The satellite's exact contract: replay a run_report
        predicted-vs-observed table."""
        cc = _load_tool('calibrate_costmodel')
        doc = {'schema_version': 1, 'collectives_cmp': {
            'all-reduce': {'observed_us': 150.0,
                           'observed_wire_bytes': 1 << 20,
                           'observed_phases': 14,
                           'predicted_wire_bytes': 1 << 20,
                           'predicted_phases': 14}}}
        path = os.path.join(tmp_path, 'report.json')
        with open(path, 'w') as f:
            json.dump(doc, f)
        out = os.path.join(tmp_path, 'cal.json')
        assert cc.main([path, '-o', out]) == 0
        table = json.load(open(out))
        assert 'all-reduce' in table['per_op']
        assert table['per_op']['all-reduce']['samples'] == 1


# -------------------------------------- goldens stay in sync
class TestPlanGoldens:
    @staticmethod
    def _goldens():
        with open(os.path.join(REPO, 'tools',
                               'plan_goldens.json')) as f:
            return json.load(f)

    def test_goldens_file_shape(self):
        """The committed goldens parse and cover the whole built-in
        suite."""
        doc = self._goldens()
        assert doc['chips'] == 8
        assert set(doc['winners']) == set(targets.TARGETS)
        for t, w in doc['winners'].items():
            assert w['assignment']
            sizes = [int(s) for s in w['mesh'].values()]
            total = 1
            for s in sizes:
                total *= s
            assert total == doc['chips'], t

    @pytest.mark.parametrize('target', list(targets.TARGETS))
    def test_top_ranked_plan_matches_golden(self, target):
        """A diff means the cost model or the planner's scoring now
        ranks shardings differently: update tools/plan_goldens.json
        deliberately, or fix the regression."""
        doc = self._goldens()
        want = doc['winners'][target]
        got = planner.plan_target(target, chips=doc['chips']) \
            .to_json()['winner']
        assert got is not None, target
        assert ({a: s for a, s in got['mesh'].items() if s > 1}
                == {a: s for a, s in want['mesh'].items() if s > 1})
        assert got['assignment'] == want['assignment']
        assert got.get('fallback') == want['fallback']
