"""The serve runner end to end at a tiny size on the CPU: the result
line's shape, and a `correct` that no schedule can flip: the same with
the profiler off, on, and under a clock that keeps jumping; false only
when the probe's reference disagrees."""
import json
import time

import pytest

from bench_helpers import JumpyClock, run_tiny, tiny_cell

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device', 'compared'}
COMPARED = {'probe_logit_gap', 'served_logit_gap', 'requests_unaccounted',
            'tokens_not_adding_up', 'audit_findings', 'pool_blocks_missing'}
E2E = {'serve_backlog': {'serve_tokens_per_s', 'setup_s'},
       'serve_chat_steady': {'tpot_p95_ms', 'setup_s'}}


def check_line(line, workload, traced):
    json.dumps(line)
    # on the CPU no device plane is traced, so no breakdown either
    assert set(line) == KEYS
    # every number `correct` was decided by, beside its limit, comes last
    assert list(line)[-1] == 'compared'
    assert set(line['compared']) == COMPARED
    assert all(value <= limit for value, limit in line['compared'].values())
    assert set(line['device']) == {'platform', 'kind', 'count',
                                   'memory_peak_bytes'}
    assert line['device']['platform'] == 'cpu'
    for m in line['metrics'].values():
        assert set(m) == {'value', 'unit'}
    if not traced:
        assert set(line['metrics']) == E2E[workload]
        assert all(m['value'] > 0 for m in line['metrics'].values())
    else:
        # counters are read; nothing under a device metric's name
        assert not any(n.startswith(('device_idle', 'peak_hbm'))
                       for n in line['metrics'])
        assert any(n.startswith('batch_occupancy')
                   for n in line['metrics'])
    assert line['attempted'] > 0
    assert line['failed'] == 0


@pytest.mark.parametrize('workload', ['serve_backlog',
                                      'serve_chat_steady'])
@pytest.mark.parametrize('how', ['plain', 'traced', 'stalled'])
def test_correct_does_not_depend_on_the_schedule(workload, how):
    kwargs = {'clock': JumpyClock()} if how == 'stalled' else {}
    line = run_tiny(workload, seconds=1.5, trace=int(how == 'traced'),
                    **kwargs)
    assert line['correct'] is True
    if how != 'stalled':
        check_line(line, workload, traced=how == 'traced')
    if workload == 'serve_backlog':
        assert line['attempted'] < 2048      # the window cut the backlog


def test_late_requests_fail_without_flipping_correct():
    # every jump is longer than window and drain together: the engine
    # gives up on whatever is in flight
    line = run_tiny('serve_chat_steady', seconds=1.5,
                    clock=JumpyClock(every=60, jump_s=40.0))
    assert line['failed'] > 0
    assert line['correct'] is True


@pytest.mark.parametrize('workload', ['serve_backlog'])
def test_a_wrong_reference_turns_correct_false(workload):
    line = run_tiny(workload, seconds=0.5, reference_perturb=0.05)
    assert line['correct'] is False
    assert line['failed'] == 0


def test_a_wrong_reference_fails_both_gaps_and_says_by_how_much():
    line = run_tiny('serve_chat_steady', seconds=0.5,
                    reference_perturb=0.05)
    assert line['correct'] is False
    for name in ('probe_logit_gap', 'served_logit_gap'):
        value, limit = line['compared'][name]
        assert value > 0.05 >= limit


def _broken_paged_attention(fault):
    """paged_attention with one fault of the cache path put in."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_attention as ops
    sound = ops.paged_attention

    def broken(q, k_pool, v_pool, block_tables, lens):
        if fault == 'zeros':
            return jnp.zeros_like(q)
        if fault == 'no_mask':          # reads past the context's end
            lens = jnp.full_like(lens, block_tables.shape[1]
                                 * k_pool.shape[2])
        elif fault == 'neighbour_slot':     # another request's blocks
            block_tables = jnp.roll(block_tables, 1, axis=0)
        elif fault == 'first_block_lost':
            block_tables = block_tables.at[:, 0].set(block_tables[:, 1])
        return sound(q, k_pool, v_pool, block_tables, lens)

    return broken


@pytest.mark.parametrize('fault', ['zeros', 'no_mask', 'neighbour_slot',
                                   'first_block_lost'])
def test_a_fault_in_the_cache_path_turns_correct_false(monkeypatch,
                                                       fault):
    """The probe has power over the paged cache: the model imports
    paged_attention when the decode modules are traced, so a broken
    one is what the engine runs, and the reference is untouched."""
    from paddle_tpu.ops import paged_attention as ops
    monkeypatch.setattr(ops, 'paged_attention',
                        _broken_paged_attention(fault))
    line = run_tiny('serve_backlog', seconds=0.5)
    assert line['correct'] is False
    # the invariants still hold: only the probe saw it
    assert line['failed'] == 0


def _only_under_load(broken, sound):
    """`broken` in every row of a batch but the first, `sound` there:
    the probe's one request at a time sits in the first row and never
    meets the fault."""
    import jax.numpy as jnp

    def attention(q, k_pool, v_pool, block_tables, lens):
        first = (jnp.arange(q.shape[0]) == 0).reshape(
            (-1,) + (1,) * (q.ndim - 1))
        return jnp.where(first,
                         sound(q, k_pool, v_pool, block_tables, lens),
                         broken(q, k_pool, v_pool, block_tables, lens))

    return attention


@pytest.mark.parametrize('fault', ['zeros', 'no_mask', 'neighbour_slot',
                                   'first_block_lost'])
def test_a_fault_under_load_is_seen_in_what_the_window_served(
        monkeypatch, fault, workload='serve_backlog'):
    """The timed path broken underneath where only load reaches: the
    probe passes, the sample of what the window finished does not."""
    from paddle_tpu.ops import paged_attention as ops
    monkeypatch.setattr(ops, 'paged_attention', _only_under_load(
        _broken_paged_attention(fault), ops.paged_attention))
    line = run_tiny(workload, seconds=1.0)
    assert line['correct'] is False and line['failed'] == 0
    probe, limit = line['compared']['probe_logit_gap']
    assert probe <= limit
    served, limit = line['compared']['served_logit_gap']
    assert served > limit


def test_a_token_altered_where_it_is_produced_turns_correct_false(
        monkeypatch):
    """Every row but the first takes the sampler's SECOND best token:
    the probe's row is the first of its batch and passes; served tokens
    lie under the reference's best by its margin."""
    import jax.numpy as jnp
    from paddle_tpu.serving import ServingEngine

    def second_best(self):
        def sample(logits, seeds, pos):
            order = jnp.argsort(logits, axis=-1)
            rows = jnp.arange(logits.shape[0])
            return jnp.where(rows == 0, order[:, -1], order[:, -2])
        return sample

    monkeypatch.setattr(ServingEngine, '_sample_fn', second_best)
    line = run_tiny('serve_backlog', seconds=1.0)
    assert line['correct'] is False and line['failed'] == 0
    assert line['compared']['probe_logit_gap'][0] == 0
    served, limit = line['compared']['served_logit_gap']
    assert served > limit


@pytest.mark.parametrize('seed', [11, 2147483659, 2147495993])
def test_the_control_in_the_precision_below_comes_out_not_correct(seed):
    """The runner's own comparison, `serve.served`, on one sample of a
    window: true on the tokens the program served, false, with the gap
    over the limit, on the tokens the reference with its matrices
    rounded to float8 puts first at the same positions
    (chip_control_serve.py makes the same two decisions at a cell's own
    size on the chip; PERF.md has them)."""
    import chip_control_serve as control
    cell = tiny_cell('serve_backlog')
    # at this size a float8 choice differs from the float32 one at one
    # position in twenty: enough rows that some do by more than the
    # limit; and a backlog short enough to be served whole, so that the
    # sample is the seed's alone and not the machine's speed's
    cell['config']['probe']['served_requests'] = 16
    cell['traffic']['num_requests'] = 32
    read = control.readings(cell, seed, 60.0)
    assert read['tokens'] >= 300
    assert read['program'][0] is True
    assert read['program'][1] <= read['limit']
    assert read['control'][0] is False
    assert read['control'][1] > read['limit']


def test_the_served_sample_is_the_seeds_and_holds_the_longest():
    from benchmark import logit_gap

    class Req:
        state = 'done'

        def __init__(self, i, plen, new, got=None):
            self.rid, self.prompt = f'w{i:05d}', list(range(plen))
            self.max_new_tokens = new
            self.tokens = list(range(new if got is None else got))

    reqs = [Req(i, 10 + i, 5) for i in range(20)] + [Req(20, 40, 5, got=3)]
    rows = logit_gap.sample(reqs, 7, 4)
    assert len(rows) == 4
    assert len(rows[0][0]) == 29            # the longest finished whole
    assert all(len(t) == 5 for _, t in rows)
    again = logit_gap.sample(reqs, 7, 4)
    assert [len(p) for p, _ in again] == [len(p) for p, _ in rows]
    other = {tuple(len(p) for p, _ in logit_gap.sample(reqs, s, 4))
             for s in range(8)}
    assert len(other) > 1                   # the seed draws the rest
    # none finished whole: what was cut with tokens served; else nothing
    assert len(logit_gap.sample(reqs[-1:], 7, 4)[0][1]) == 3
    assert logit_gap.sample([Req(0, 9, 5, got=0)], 7, 4) == []


def test_the_gap_is_read_at_the_positions_that_chose_the_tokens():
    """The shared routine against a reference made by hand: logits that
    put id (position + 1) % 7 first by 1.0 and every other id 0.5 under
    the second.  Served tokens that follow the rule read 0, one that
    does not reads its distance, `judged` tokens are read in the served
    ones' place, and of a long row only the ends where `keep` says."""
    import numpy as np
    import jax.numpy as jnp
    from benchmark import logit_gap

    def logits_at(ids, positions):
        best = (np.asarray(positions) + 1) % 7
        out = np.full(positions.shape + (7,), -0.5, np.float32)
        np.put_along_axis(out, best[:, :, None], 1.0, axis=2)
        np.put_along_axis(out, ((best + 1) % 7)[:, :, None], 0.0, axis=2)
        return jnp.asarray(out)

    def row(plen, new):
        return (np.arange(plen) % 7, [(plen + j) % 7 for j in range(new)])

    rows = [row(5, 6), row(9, 4), row(3, 6)]
    rows[2][1][4] = (rows[2][1][4] + 1) % 7         # the second best
    gaps, same, margins, _ = logit_gap.gaps(logits_at, rows, 16, 6)
    assert gaps.shape == (3, 6) and same == 15 and margins.size == 16
    assert gaps[:2].max() == 0 and gaps[2].tolist() == [0, 0, 0, 0, 1, 0]
    ok, _ = logit_gap.check('g', logits_at, rows, 0.5, lambda m: None,
                            compared := {}, width=16, keep=6)
    assert ok is False and compared == {'g': [1.0, 0.5]}
    # the control's tokens in the served ones' place: the third best
    judged = [(np.asarray(t) + 2) % 7 for _p, t in rows[:2]]
    gaps, same, _, _ = logit_gap.gaps(logits_at, rows[:2], 16, 6,
                                      judged=judged)
    assert same == 0 and gaps[0].tolist() == [1.5] * 6
    assert [c.tolist() for c in logit_gap.first_choices(
        logits_at, rows[:2], 16, 6)] == [rows[0][1], rows[1][1]]
    # a row longer than `keep`: its first and last keep / 2 tokens
    assert logit_gap.ends(list(range(10)), 4).tolist() == [0, 1, 8, 9]
    long = row(4, 10)
    long[1][5] = (long[1][5] + 1) % 7               # in the middle
    assert logit_gap.gaps(logits_at, [long], 16, 4)[0].max() == 0
    long[1][9] = (long[1][9] + 1) % 7               # the last
    assert logit_gap.gaps(logits_at, [long], 16, 4)[0].tolist() == [
        [0, 0, 0, 1]]
    ok, _ = logit_gap.check('g', logits_at, [], 0.5, lambda m: None,
                            compared, width=16, keep=6)
    assert ok is False and compared['g'][0] == float('inf')


def test_the_weights_are_the_benchmarks_and_the_program_holds_them():
    """`build` draws every tensor from the seed in the served dtype,
    the program loads them without a copy, and the same seed draws the
    same, another seed others, none all 0 or all 1."""
    import numpy as np
    from benchmark.runners import serve
    config = tiny_cell('serve_backlog')['config']
    model, engine, weights = serve.build(config, 2147495993, time.monotonic)
    held = engine._params
    assert set(held) == set(weights) == set(model.state_dict())
    for name, w in weights.items():
        assert str(w.dtype) == config['weights_dtype']
        assert held[name] is w, name
        assert np.asarray(w, np.float32).std() > 0, name
    std = {n: float(np.asarray(w, np.float32).std())
           for n, w in weights.items()}
    wide = config['model']['initializer_range']
    assert 0.9 * wide < std['gpt.wte.weight'] < 1.1 * wide
    assert 0.9 * wide < 2 * std['gpt.blocks.1.mlp.proj.weight'] < 1.1 * wide
    _m, _e, again = serve.build(config, 2147495993, time.monotonic)
    _m, _e, other = serve.build(config, 2147495994, time.monotonic)
    assert all((again[n] == weights[n]).all() for n in weights)
    assert not any((other[n] == weights[n]).all() for n in weights)


@pytest.mark.parametrize('fault', ['scaled', 'transposed', 'not_loaded'])
def test_a_tensor_the_program_loads_wrongly_turns_correct_false(
        monkeypatch, fault):
    """The reference reads the weights as the benchmark drew them, not
    what the program holds: positions loaded 1.5 times too large, a
    square matrix loaded transposed, or the program's own
    initialisation left in place, each shows as a gap."""
    from paddle_tpu.models.gpt import GPTForCausalLM
    sound = GPTForCausalLM.set_state_dict

    def load(self, state):
        if fault == 'not_loaded':
            return [], []
        state = dict(state)
        for name, t in state.items():
            if fault == 'scaled' and name == 'gpt.wpe.weight':
                state[name] = t * 1.5
            if fault == 'transposed' and name.endswith('attn.proj.weight'):
                state[name] = type(t)(t.value.T)
        return sound(self, state)

    monkeypatch.setattr(GPTForCausalLM, 'set_state_dict', load)
    line = run_tiny('serve_backlog', seconds=0.5)
    assert line['correct'] is False and line['failed'] == 0
    probe, limit = line['compared']['probe_logit_gap']
    assert probe > limit


def test_the_engines_clock_keeps_its_three_longest_gaps():
    from benchmark.runners.serve import EngineClock
    t = [0.0]
    clock = EngineClock(lambda: t[0])
    for now in (0.0, 0.1, 0.15, 1.15, 1.2, 1.5, 1.52, 3.52):
        t[0] = now
        clock()
    assert [round(g, 2) for g, _ in clock.gaps] == [2.0, 1.0, 0.3]
    assert [round(at, 2) for _, at in clock.gaps] == [1.52, 0.15, 1.2]


def test_a_traced_run_counts_up_to_the_profilers_start():
    """The profiler holds the engine while it starts and stops, and an
    open loop then works off a queue: the per-layer counters of a
    traced run are taken at the moment before the profiler starts."""
    from benchmark.runners.serve import TRACE_SECONDS, EngineClock

    class Tracer:
        open = done = False
        stall_s = 0.5

        def start(self):
            self.open = True

        def stop(self):
            self.open, self.done = False, True

    t = [0.0]
    clock = EngineClock(lambda: t[0])
    clock.tracer, clock.trace_at = Tracer(), 1.0
    clock.counted = lambda now: {'t': now, 'interventions': 7}
    assert clock() == 0.0 and clock.before_trace is None
    t[0] = 1.5
    clock()
    assert clock.before_trace == {'t': 1.5, 'interventions': 7}
    assert clock.tracer.open
    t[0] = 1.0 + TRACE_SECONDS + 0.4     # the stall does not count
    clock()
    assert clock.tracer.open
    t[0] += 0.2
    clock()
    assert clock.tracer.done and clock.before_trace['t'] == 1.5


@pytest.mark.parametrize('workload, whole', [('serve_backlog', True),
                                             ('serve_chat_steady', False)])
def test_which_part_of_a_traced_run_is_counted(monkeypatch, workload,
                                               whole):
    """Where requests arrive during the window the counters stop at the
    profiler's start (0.4 of the window); a backlog due at once counts
    the whole of run().  The profiler is put out of the way, so that
    its own stalls on the CPU do not move the clock."""
    from benchmark import harness
    from benchmark.runners import serve

    class NoProfiler:
        stall_s = 0.0
        open = done = False

        def __init__(self, workload):
            pass

        def start(self):
            self.open = True

        def stop(self):
            self.open, self.done = False, True

        def load(self):
            return None

    monkeypatch.setattr(harness, 'TraceWindow', NoProfiler)
    run = serve.run(tiny_cell(workload), 7, 2.0, True, time.monotonic(),
                    say=lambda msg: None)
    assert run['correct'] is True
    counted_s = run['counters']['window_ms'] / 1e3
    assert counted_s >= 1.9 if whole else 0.8 <= counted_s < 1.6

