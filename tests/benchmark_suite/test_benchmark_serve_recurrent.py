"""The serve_recurrent runner end to end at a tiny size on the CPU,
through tiny_backlog: the result line's shape, a `correct` that the
profiler does not flip and that turns false when the reference
disagrees or the program leaves out a part of the mathematics (the
gate, the normaliser, the sqrt 2 cross terms) or writes a state into
the wrong slot; a state held in bfloat16 turns it false by the
probe's second limit alone; a state that survives its slot's reuse,
which the probe cannot meet, turns it false by the tokens served in the
window; and retention_flops against a count by hand."""
import os
import time

import pytest

from bench_helpers import HERE

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device', 'compared'}
CELL = 'serve_backlog_retention'


def tiny_cell():
    from benchmark import harness
    cell = harness.load_cell(CELL)
    cell['config'] = harness.load_json(os.path.join(
        HERE, 'configs', 'tiny_serve_recurrent.json'))
    cell['traffic'] = harness.load_json(os.path.join(
        HERE, 'traffic', 'tiny_backlog.json'))
    return cell


def run_tiny(seed=2147495993, seconds=1.0, trace=0, cell=None,
             **runner_kwargs):
    from benchmark import run
    return run.run_cell(cell or tiny_cell(), seed, seconds, trace,
                        time.monotonic(), **runner_kwargs)


@pytest.mark.parametrize('how', ['plain', 'traced'])
def test_the_cell_runs_and_is_correct(how):
    line = run_tiny(seconds=1.5, trace=int(how == 'traced'))
    assert set(line) == KEYS
    assert list(line)[-1] == 'compared'
    assert {'probe_logit_gap', 'state_numerator_rel', 'served_logit_gap',
            'state_denominator_rel'} <= set(line['compared'])
    assert all(v <= limit for v, limit in line['compared'].values())
    assert line['correct'] is True
    assert line['failed'] == 0 and 0 < line['attempted'] < 2048
    assert line['device']['platform'] == 'cpu'
    names = set(line['metrics'])
    if how == 'plain':
        assert names == {'serve_tokens_per_s', 'setup_s'}
    else:
        # counters are read; nothing under a device metric's name
        assert names == {'state_rows_per_token_step.retention',
                         'compiles_in_window.retention',
                         'intervention_ms.retention',
                         'batch_occupancy.retention'}
        rows = line['metrics']['state_rows_per_token_step.retention']
        assert 2.0 < rows['value'] <= 4.0           # of 4 slots
        assert line['metrics']['compiles_in_window.retention'][
            'value'] == 0


def test_a_wrong_reference_turns_correct_false():
    line = run_tiny(seconds=0.5, reference_perturb=0.05)
    assert line['correct'] is False and line['failed'] == 0


def _break(monkeypatch, fault):
    """One fault put into the program; the reference is untouched."""
    import jax.numpy as jnp
    from paddle_tpu.ops import power_retention as pr
    from paddle_tpu.serving.kv_cache import RecurrentStateCache
    if fault == 'no_gate':              # no decay: g = 0
        for name in ('retention_prefill', 'retention_decode'):
            sound = getattr(pr, name)
            monkeypatch.setattr(
                pr, name, lambda q, k, v, g, *a, _f=sound, **kw:
                _f(q, k, v, jnp.zeros_like(g), *a, **kw))
    elif fault == 'no_normaliser':      # y = num / (den + huge)
        monkeypatch.setattr(pr, 'EPS_R', 1e6)
    elif fault == 'no_cross_weights':   # the sqrt 2 of x_a x_b lost
        sound = pr.phi

        def unweighted(x):
            d = x.shape[-1]
            rows = pr.feature_rows(d)
            out = sound(x).reshape(*x.shape[:-1], rows, d)
            scale = jnp.ones((rows, 1)).at[1:rows - 1].set(0.5 ** 0.5)
            return (out * scale).reshape(*x.shape[:-1], rows * d)

        monkeypatch.setattr(pr, 'phi', unweighted)
    elif fault == 'neighbour_slot':     # prefill writes the next slot
        sound = RecurrentStateCache.prefill_where

        def shifted(self, seq_ids, rows, bucket):
            return (sound(self, seq_ids, rows, bucket) + 1) % self.slots

        monkeypatch.setattr(RecurrentStateCache, 'prefill_where', shifted)
    elif fault == 'pads_reach_the_state':
        sound = pr.retention_prefill
        monkeypatch.setattr(
            pr, 'retention_prefill', lambda q, k, v, g, lengths, **kw:
            sound(q, k, v, g, jnp.full_like(lengths, q.shape[1]), **kw))


@pytest.mark.parametrize('fault', ['no_gate', 'no_normaliser',
                                   'no_cross_weights', 'neighbour_slot',
                                   'pads_reach_the_state'])
def test_a_fault_in_the_retention_path_turns_correct_false(monkeypatch,
                                                           fault):
    """The probe has power over the mechanism: the model reaches the op
    through its module when the engine's modules are traced, so a
    broken one is what the engine runs."""
    _break(monkeypatch, fault)
    line = run_tiny(seconds=0.5)
    assert line['correct'] is False
    # the invariants still hold: only the probe saw it
    assert line['failed'] == 0


def test_a_state_held_in_bfloat16_turns_correct_false(monkeypatch):
    """The control of `probe.state_rel_tol`: the precision below the
    one the configuration states.  The logit gap does not see it (that
    is why the second limit exists); the held state's distance from its
    definition does, by more than ten times the limit, where a float32
    state stays a hundred times under it."""
    import jax.numpy as jnp
    from paddle_tpu.serving.kv_cache import RecurrentStateCache
    line = run_tiny(seconds=0.5)
    monkeypatch.setattr(RecurrentStateCache, 'dtype', jnp.bfloat16)
    cell = tiny_cell()
    cell['config']['state']['dtype'] = 'bfloat16'
    broken = run_tiny(seconds=0.5, cell=cell)
    assert line['correct'] is True and broken['correct'] is False

    def readings(compared):
        gap, gap_tol = compared['probe_logit_gap']
        assert gap <= gap_tol
        (num, tol), (den, _) = (compared['state_numerator_rel'],
                                compared['state_denominator_rel'])
        return max(num, den), tol

    err, tol = readings(line['compared'])
    assert err < tol / 30             # float32 rounding, 1e-6
    err, tol = readings(broken['compared'])
    assert err > 10 * tol             # bfloat16 rounding, 2e-3


def test_a_state_that_survives_its_slots_reuse_turns_correct_false(
        monkeypatch):
    """A fault only load can show: the prefill's write is dropped for a
    slot that has held a sequence before, so its next sequence decodes
    from the last one's state.  The probe's three requests take three
    fresh slots and pass; the tokens served in the window do not."""
    import jax.numpy as jnp
    from paddle_tpu.serving.kv_cache import RecurrentStateCache
    sound = RecurrentStateCache.store_prefill

    def stale(self, arrays, caches, where):
        written = sound(self, arrays, caches, where)
        rows = jnp.minimum(where, self.slots - 1)
        # a slot nobody has held is all zeros (the cache starts so)
        fresh = (arrays[1][0][rows] == 0).all(axis=(1, 2))       # [B]

        def keep(new, old):
            pick = fresh.reshape((-1,) + (1,) * (new.ndim - 1))
            return new.at[where].set(
                jnp.where(pick, new[rows], old[rows]), mode='drop')

        return tuple(tuple(keep(n, o) for n, o in zip(ns, os))
                     for ns, os in zip(written, arrays))

    monkeypatch.setattr(RecurrentStateCache, 'store_prefill', stale)
    line = run_tiny(seconds=1.0)
    assert line['correct'] is False and line['failed'] == 0
    probe, limit = line['compared']['probe_logit_gap']
    assert probe <= limit
    # three requests sampled, their slots reused by then: most tokens
    # are not the reference's best
    served, limit = line['compared']['served_logit_gap']
    assert served > 2 * limit


def test_retention_flops_against_a_count_by_hand():
    from benchmark import retention_flops as rf
    m = {'head_dim': 4, 'num_heads': 4, 'num_kv_heads': 2}
    D = 10                              # 4 squares and 6 pairs
    assert rf.features(4) == D and rf.features(128) == 8256
    ops, moved = rf.decode_update(m)
    # S [10, 4] and z [10] a head, two heads, in and out; q and y 16
    # each; k and v 8 each; 2 gates
    assert moved == 4 * (2 * 2 * 10 * 5 + 2 * 16 + 2 * 8 + 2)
    # scale and rank-one add 3 x 50 a head; feature maps 2 D for 2 + 4
    # heads; read-out 2 x 10 x 5 a query head
    assert ops == 3 * 2 * 50 + 2 * 10 * 6 + 2 * 4 * 50
    ops, moved = rf.prefill_call(m, 8)
    assert ops == 4 * 4 * 64 * 4 // 2 + 2 * 2 * 8 * 50 + 2 * 2 * 8 * 10
    assert moved == 4 * (2 * 4 * 8 * 4 + 2 * 2 * 8 * 4 + 2 * 8 + 2 * 50)
    # the cell's update is bound by memory: 68.2 MB a row and layer
    big = {'head_dim': 128, 'num_heads': 40, 'num_kv_heads': 8}
    ops, moved = rf.decode_update(big)
    assert 68.0e6 < moved < 68.4e6
    least, bound = rf.least_seconds(ops, moved, rf.peaks('TPU v5 lite'))
    assert bound == 'memory' and 83e-6 < least < 84e-6


def test_the_roofline_reader_counts_live_rows_not_the_bucket():
    from benchmark.readers import retention_decode_roofline as reader

    class Trace:
        def scope_ns(self, pattern):
            assert pattern == 'retention\\.decode'
            return 8 * 16 * 8 * 100e3, 64      # 100 us a row and layer

        def begun(self, name):
            assert name == 'serve.decode_dispatch'
            return [None] * 2

    cfg = {'model': {'head_dim': 128, 'num_heads': 40, 'num_kv_heads': 8,
                     'num_layers': 8}, 'serve': {'decode_span': 4}}
    ctx = {'scoped_trace': Trace(), 'config': cfg,
           'device_kind': 'TPU v5 lite',
           'counters': {'state_rows_updated': 1600, 'token_steps': 100}}
    params = {'scope': 'retention\\.decode',
              'per_span': 'serve.decode_dispatch'}
    full = reader.read(params, ctx)
    assert 83.0 < full < 84.0          # 83.3 us of 100 at 16 live rows
    ctx['counters']['state_rows_updated'] = 800     # 8 live of 16
    assert reader.read(params, ctx) == pytest.approx(full / 2)
    ctx['counters'] = {}
    assert reader.read(params, ctx) is None
    assert reader.read(params, dict(ctx, scoped_trace=None)) is None
