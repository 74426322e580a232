"""The traffic generators: seeded, the same schedule for every seed,
and nothing the engine would refuse."""
import json
import os
from collections import Counter

import numpy as np
import pytest

from bench_helpers import REPO

BIG_SEED = 2 ** 31 + 12345


def load(kind, name):
    with open(os.path.join(REPO, 'benchmark', kind, name + '.json')) as f:
        return json.load(f)


def sizes(reqs):
    return Counter((r.prompt.size, r.max_new_tokens) for r in reqs)


@pytest.mark.parametrize('mix', ['backlog_long', 'chat_poisson'])
def test_requests_are_seeded_and_fit_the_engine(mix):
    from benchmark.generators import requests
    traffic = load('traffic', mix)
    serve = load('configs', 'cerebras_gpt_1p3b_serve')['serve']
    a = requests.make(traffic, BIG_SEED, 45)
    b = requests.make(traffic, BIG_SEED, 45)
    c = requests.make(traffic, 7, 45)
    assert [r.rid for r in a] == [r.rid for r in b]
    for x, y in zip(a, b):
        assert x.arrival_t == y.arrival_t
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    # another seed: the same schedule, other token ids
    assert [(r.prompt.size, r.max_new_tokens, r.arrival_t) for r in a] \
        == [(r.prompt.size, r.max_new_tokens, r.arrival_t) for r in c]
    assert not np.array_equal(a[0].prompt[:8], c[0].prompt[:8])
    if len(a) % requests.STRATUM == 0:
        for k in (1, 2, 5):     # whole strata hold every quantile once
            cut = k * requests.STRATUM
            assert sizes(a[:cut]) == {
                pair: k * c for pair, c
                in sizes(a[:requests.STRATUM]).items()}
    for r in a:
        assert r.prompt.size + r.max_new_tokens <= serve['max_model_len']
        assert r.prompt.size <= max(serve['prompt_buckets'])
        assert 0 <= r.prompt.min() and r.prompt.max() < traffic['id_limit']
        assert 0 <= r.arrival_t <= 45 + 1e-9
    arrivals = [r.arrival_t for r in a]
    assert arrivals == sorted(arrivals)
    if traffic['arrivals']['kind'] == 'poisson':
        assert len(a) == round(traffic['arrivals']['rate_rps'] * 45)
        assert arrivals[-1] == pytest.approx(45)


def test_length_quantiles_follow_the_file():
    from benchmark.generators import requests
    chat = load('traffic', 'chat_poisson')
    p = requests.lengths(chat['prompt_len'], 1024)
    assert p.min() >= 32 and p.max() <= 1024
    assert abs(np.median(p) - 256) <= 2
    long = load('traffic', 'backlog_long')
    q = requests.lengths(long['new_tokens'], 1024)
    assert q.min() >= 64 and q.max() <= 512
    assert abs(np.median(q) - np.sqrt(64 * 512)) <= 2


def test_token_batches_are_seeded():
    from benchmark.generators import token_batches
    traffic = load('traffic', 'steps_random_tokens')
    a = token_batches.make(traffic, BIG_SEED)
    b = token_batches.make(traffic, BIG_SEED)
    assert a.shape == (4, 2048)
    assert np.array_equal(a.batch(3), b.batch(3))
    assert not np.array_equal(a.batch(3), a.batch(4))
    assert not np.array_equal(a.batch(3),
                              token_batches.make(traffic, 1).batch(3))
    assert a.batch(0).max() < traffic['id_limit'] <= 50257
