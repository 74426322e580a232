"""Serving input: one general request generator, driven by the traffic
file's parameters.

Every seed gets THE SAME SCHEDULE: the same (prompt length, new tokens)
pairs due at the same times.  Lengths are the evenly spaced quantiles
of the file's distributions: a stratum of STRATUM requests holds each
quantile once, in an order and a pairing fixed here (PAIRING_SEED), and
the strata repeat.  Gaps are the quantiles of the arrival process, in
an order fixed the same way.  The seed draws the token ids (and the
runner's weights) and nothing that moves a clock: the same bursts and
the same crowds of long requests meet in every run.

Measured, PR 24, why the seed may not order anything.  Shuffling the
requests by the seed put the chat cell's 95th percentiles 17% apart
while one seed repeated within 0.5%.  Turning a backlog that is due all
at once put tokens/s 15% apart, one seed within 0.3%: a window serves a
twentieth of it, so the order is the schedule.  Even entering the one
fixed cycle of arrivals at a point the seed chose left the pace's tail
6% apart between seeds, 0.1 to 1.3% within one.  The driver reads the
spread across seeds, so whatever the seed moves is paid for in the
bound.
"""
import statistics

import numpy as np

PAIRING_SEED = 7    # fixes order and pairing; no file needs another
STRATUM = 32        # requests in a row that hold every quantile once


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lengths(dist, n):
    """The n evenly spaced quantiles of a length distribution, as
    whole numbers inside [lo, hi]."""
    q = _quantiles(n)
    lo, hi = float(dist['lo']), float(dist['hi'])
    if dist['kind'] == 'loguniform':
        vals = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    elif dist['kind'] == 'lognormal':
        z = np.asarray([statistics.NormalDist().inv_cdf(p) for p in q])
        vals = float(dist['median']) * np.exp(float(dist['sigma']) * z)
    else:
        raise ValueError(f'unknown length distribution {dist["kind"]!r}')
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def gaps(spec, n, file_rng):
    """The n gaps before each arrival, in an order every seed shares."""
    if spec['kind'] == 'all_at_zero':
        return np.zeros(n)
    if spec['kind'] == 'poisson':
        # the quantiles of the exponential, scaled to mean 1/rate
        g = -np.log1p(-_quantiles(n))
        g *= 1.0 / (g.mean() * float(spec['rate_rps']))
        return file_rng.permutation(g)
    raise ValueError(f'unknown arrival process {spec["kind"]!r}')


def count(traffic, seconds):
    """How many requests a window of `seconds` is offered."""
    arr = traffic['arrivals']
    if arr['kind'] == 'all_at_zero':
        return int(traffic['num_requests'])
    return max(1, int(round(float(arr['rate_rps']) * float(seconds))))


def make(traffic, seed, seconds, rid_prefix='w'):
    """The window's requests, sorted by due time.  Every request fits
    the engine (prompt + new <= max_model_len is checked here, against
    the traffic file's own `max_context`)."""
    from paddle_tpu.serving.scheduler import Request
    n = count(traffic, seconds)
    prompts = lengths(traffic['prompt_len'], STRATUM)
    news = lengths(traffic['new_tokens'], STRATUM)
    # which prompt meets which answer is never the seed's choice
    file_rng = np.random.default_rng(PAIRING_SEED)
    news = news[file_rng.permutation(STRATUM)]
    over = prompts + news > int(traffic['max_context'])
    if over.any():
        raise ValueError(
            f'{int(over.sum())} length pairs exceed max_context '
            f'{traffic["max_context"]}')
    order = file_rng.permutation(STRATUM)
    idx = order[np.arange(n) % STRATUM]
    due = np.cumsum(gaps(traffic['arrivals'], n, file_rng))
    ids = np.random.default_rng([int(seed), 1])
    id_limit = int(traffic['id_limit'])
    out = []
    for i in range(n):
        p, m = int(prompts[idx[i]]), int(news[idx[i]])
        out.append(Request(
            f'{rid_prefix}{i:05d}',
            ids.integers(0, id_limit, size=p, dtype=np.int64), m,
            arrival_t=float(due[i])))
    return out
