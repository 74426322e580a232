"""The serve runner end to end at a tiny size on the CPU: the result
line's shape, and a `correct` that no schedule can flip: the same with
the profiler off, on, and under a clock that keeps jumping; false only
when the probe's reference disagrees."""
import json
import time

import pytest

from bench_helpers import JumpyClock, run_tiny, tiny_cell

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}
E2E = {'serve_backlog': {'serve_tokens_per_s', 'setup_s'},
       'serve_chat_steady': {'tpot_p95_ms', 'setup_s'}}


def check_line(line, workload, traced):
    json.dumps(line)
    # on the CPU no device plane is traced, so no breakdown either
    assert set(line) == KEYS
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

