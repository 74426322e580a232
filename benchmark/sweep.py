#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip:

    python3 benchmark/sweep.py --workload serve_chat_steady --rates 13,14,15,16,17,18,19 --span 60

One engine is warmed once; each rate is then offered for `span` seconds
(the cell's own traffic file with `rate_rps` replaced, made by the
cell's own generator) and cut there.  One JSON line a rate: offered,
completed, still running and still waiting at the cut, tokens/s offered
and decoded, the mean number of requests waiting for their first token
in the second and in the last quarter of the span, the pace's 95th
percentile over the requests that finished, and the TTFT p95 of the
requests due in each half.

The knee is the highest rate whose backlog does not grow: with every
lower rate, the mean number waiting in the last quarter is at most
GROWTH more than in the second quarter.  Beyond capacity the queue
gains (rate - capacity) * span / 2 requests between the two, some 7 at
a quarter of a request a second too many over 60 s; below it both are
the few that arrived during the running intervention (under 2.5 up to
16 requests/s in PR 30's sweeps, 29 at 17: the criterion read the knee
at twelve times PR 24's rates as it stands).  The cell's
`rate_rps` is four fifths of the knee, written into the traffic file by
hand; the table goes into PERF.md.
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GROWTH = 2.0


def mean_waiting(reqs, t0, t1, every=0.25):
    """Mean over [t0, t1) of the requests due and still without a first
    token, sampled on the engine's clock."""
    ticks, total = 0, 0
    t = t0
    while t < t1:
        total += sum(1 for r in reqs if r.arrival_t <= t and (
            r.first_token_t is None or r.first_token_t > t))
        ticks += 1
        t += every
    return total / max(1, ticks)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--span', type=float, default=60.0)
    ap.add_argument('--seed', type=int, default=1)
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.generators import requests as gen
    from benchmark.runners import serve
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('sweep: no TPU, no rates')
    from paddle_tpu.core import compile_cache
    compile_cache.setup_xla_cache()
    cell = harness.load_cell(args.workload)
    _model, engine, _weights = serve.build(cell['config'], args.seed,
                                           time.monotonic)
    engine.warmup()
    knee, growing = None, False
    for i, rate in enumerate(float(r) for r in args.rates.split(',')):
        traffic = copy.deepcopy(cell['traffic'])
        traffic['arrivals']['rate_rps'] = rate
        reqs = gen.make(traffic, args.seed + i, args.span,
                        rid_prefix=f's{i}_')
        due = {r.rid: r.arrival_t for r in reqs}
        report = engine.run(reqs, timeout_s=args.span)
        # run() moved every due time onto the engine's clock
        start = reqs[0].arrival_t - due[reqs[0].rid]
        halves = ([], [])
        for r in reqs:
            if r.first_token_t is not None:
                halves[due[r.rid] >= args.span / 2].append(
                    (r.first_token_t - r.arrival_t) * 1e3)
        cut = [r for r in reqs if r.state != 'done']
        tpot = [(r.finish_t - r.first_token_t) / (len(r.tokens) - 1) * 1e3
                for r in reqs if r.state == 'done' and len(r.tokens) > 1]
        quarter = args.span / 4
        early = mean_waiting(reqs, start + quarter, start + 2 * quarter)
        late = mean_waiting(reqs, start + 3 * quarter,
                            start + args.span)
        growing = growing or late > early + GROWTH
        if not growing:
            knee = rate
        print(json.dumps({
            'rate_rps': rate, 'offered': len(reqs),
            'completed': len(reqs) - len(cut),
            'running_at_cut': sum(1 for r in cut if r.tokens),
            'waiting_at_cut': sum(1 for r in cut if not r.tokens),
            'waiting_2nd_quarter': early, 'waiting_last_quarter': late,
            'backlog_grows': late > early + GROWTH,
            'offered_tokens_per_s': sum(r.max_new_tokens for r in reqs)
            / args.span,
            'tokens_per_s': report['decoded_tokens'] / report['wall_s'],
            'tpot_p95_ms': harness.percentile(tpot, .95) if tpot else None,
            'ttft_p95_ms_first_half': harness.percentile(halves[0], .95)
            if halves[0] else None,
            'ttft_p95_ms_second_half': harness.percentile(halves[1], .95)
            if halves[1] else None,
            'interventions': report['interventions'],
            'audit': report['audit']}), flush=True)
    print(json.dumps({'knee_rps': knee, 'four_fifths': None
                      if knee is None else 0.8 * knee}), flush=True)


if __name__ == '__main__':
    main()
