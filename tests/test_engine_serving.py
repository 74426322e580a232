"""Serving runtime: paged KV cache + ragged paged attention +
continuous batching (paddle_tpu/serving, ops/paged_attention).

Contracts pinned here:

- the ragged paged attention op is BIT-EXACT vs the dense cached
  attention on shared prefixes (the PR-7 masked-tail-zeros argument);
- the block allocator never leaks, never aliases two sequences to one
  block, survives seeded random admit/append/evict churn;
- the engine's greedy decode is bit-exact vs sequential batch-1
  ``generate`` on the same requests — while continuously batching a
  churning live set (admissions, evictions, backfill, EOS, deadline
  breaches, preemption);
- ``generate`` itself now routes through the factored
  ``prefill()``/``decode_step()`` the engine shares (and stays
  bit-exact — TestGPTGenerate in test_kv_cache.py pins the numbers);
- the declared bucket set AOT-precompiles into the PR-7 cache and a
  fresh engine warm-starts off it; ``tools/precompile.py --serve``
  commits auditable sidecar entries (``check_ckpt --deep`` exit 0);
- the serving decode step lints clean across the bucket set (zero
  recompile hazards) and is a plannable/auditable analysis target.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.ops.paged_attention import (gather_dense,
                                            paged_attention, write_kv)
from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                PagedCacheView, PagedKVCache, Request,
                                ServeConfig, ServingEngine,
                                poisson_requests)
from paddle_tpu.serving.kv_cache import TRASH_BLOCK, blocks_for


def _tiny_model(**kw):
    kw.setdefault('num_layers', 2)
    kw.setdefault('hidden_size', 32)
    kw.setdefault('num_heads', 2)
    kw.setdefault('max_seq_len', 64)
    paddle.seed(7)
    m = gpt_tiny(**kw)
    m.eval()
    return m


def _tiny_config(**kw):
    kw.setdefault('block_size', 4)
    kw.setdefault('max_slots', 4)
    kw.setdefault('decode_span', 2)
    kw.setdefault('prompt_buckets', (4, 8))
    kw.setdefault('batch_buckets', (1, 2, 4))
    kw.setdefault('prefill_batch', 2)
    kw.setdefault('max_model_len', 32)
    kw.setdefault('temperature', 0.0)
    return ServeConfig(**kw)


def _ref_tokens(model, prompt, n):
    out = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=n, temperature=0)
    return np.asarray(out.value)[0, prompt.size:].tolist()


class TestPagedAttentionOp:
    def _pool(self, rs, nb=9, nh=2, bs=4, hd=8):
        import jax.numpy as jnp
        # [num_blocks, block_size, num_heads, head_dim]
        k = jnp.asarray(rs.randn(nb, bs, nh, hd).astype(np.float32))
        v = jnp.asarray(rs.randn(nb, bs, nh, hd).astype(np.float32))
        return k, v

    def test_write_then_gather_roundtrip(self):
        import jax.numpy as jnp
        rs = np.random.RandomState(0)
        k, v = self._pool(rs)
        tables = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
        slots = jnp.asarray([5, 2], jnp.int32)   # blk 1 off 1, blk 0
        kn = jnp.asarray(rs.randn(2, 2, 8).astype(np.float32))
        vn = jnp.asarray(rs.randn(2, 2, 8).astype(np.float32))
        k2, v2 = write_kv(k, v, kn, vn, tables, slots)
        dk = gather_dense(k2, tables)            # [2, nh, 12, hd]
        np.testing.assert_array_equal(np.asarray(dk[0, :, 5]),
                                      np.asarray(kn[0]))
        np.testing.assert_array_equal(
            np.asarray(gather_dense(v2, tables)[1, :, 2]),
            np.asarray(vn[1]))
        # untouched slots unchanged
        np.testing.assert_array_equal(np.asarray(k2[1, 0]),
                                      np.asarray(k[1, 0]))
        # the rows lie at (block, position): slot 5 of row 0 is
        # position 1 of its second block, slot 2 of row 1 position 2
        # of its first
        np.testing.assert_array_equal(np.asarray(k2[2, 1]),
                                      np.asarray(kn[0]))
        np.testing.assert_array_equal(np.asarray(v2[3, 2]),
                                      np.asarray(vn[1]))

    @pytest.mark.parametrize('bucket', [8, 16])
    def test_store_prefill_then_gather_returns_the_dense_rows(
            self, bucket):
        """A prefill's dense buffers [B, nh, Pc, hd], stored through
        each row's block ids and gathered back through the same ids
        as a table, come back row for row, value for value; blocks no
        row names are untouched."""
        import jax.numpy as jnp
        rs = np.random.RandomState(4)
        L, nh, hd, bs, B = 2, 2, 8, 4, 2
        nblk = bucket // bs
        cache = PagedKVCache(L, nh, hd, block_size=bs,
                             num_blocks=1 + 2 * B * nblk)
        assert cache.pools[0][0].shape == (1 + 2 * B * nblk, bs, nh, hd)
        dense = [(jnp.asarray(rs.randn(B, nh, bucket, hd), jnp.float32),
                  jnp.asarray(rs.randn(B, nh, bucket, hd), jnp.float32))
                 for _ in range(L)]
        where = jnp.asarray(rs.permutation(
            np.arange(1, 1 + 2 * B * nblk))[:B * nblk]
            .reshape(B, nblk).astype(np.int32))
        ks, vs = cache.store_prefill(cache.arrays(), dense, where)
        named = np.zeros(cache.num_blocks, bool)
        named[np.asarray(where).ravel()] = True
        for (kd, vd), kp, vp in zip(dense, ks, vs):
            np.testing.assert_array_equal(
                np.asarray(gather_dense(kp, where)), np.asarray(kd))
            np.testing.assert_array_equal(
                np.asarray(gather_dense(vp, where)), np.asarray(vd))
            assert not np.asarray(kp)[~named].any()

    def test_bitexact_vs_dense_masked_attention(self):
        """paged_attention == the dense -1e9-masked softmax attention
        (models/gpt.py cached path) on the same keys — bitwise."""
        import math
        import jax
        import jax.numpy as jnp
        rs = np.random.RandomState(1)
        S, nh, hd, bs, mb = 3, 2, 8, 4, 3
        lens = np.array([5, 1, 9])
        nb = S * mb + 1
        k_pool, v_pool = self._pool(rs, nb=nb, nh=nh, bs=bs, hd=hd)
        tables = jnp.asarray(
            np.arange(1, 1 + S * mb).reshape(S, mb), jnp.int32)
        q = jnp.asarray(rs.randn(S, nh, hd).astype(np.float32))
        out = paged_attention(q, k_pool, v_pool, tables,
                              jnp.asarray(lens, jnp.int32))
        # dense reference, the gpt cached-attention formula verbatim
        kd = np.asarray(gather_dense(k_pool, tables))
        vd = np.asarray(gather_dense(v_pool, tables))
        scores = jnp.einsum('shd,shkd->shk', q, jnp.asarray(kd)) \
            * (1.0 / math.sqrt(hd))
        cols = np.arange(mb * bs)
        mask = jnp.asarray(cols[None, :] < lens[:, None])
        scores = jnp.where(mask[:, None, :], scores, -1e9)
        ref = jnp.einsum('shk,shkd->shd',
                         jax.nn.softmax(scores, axis=-1),
                         jnp.asarray(vd))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_trash_block_write_is_harmless(self):
        import jax.numpy as jnp
        rs = np.random.RandomState(2)
        k, v = self._pool(rs)
        live = np.asarray(k[1:])
        tables = jnp.zeros((2, 3), jnp.int32)     # all trash
        kn = jnp.asarray(rs.randn(2, 2, 8).astype(np.float32))
        k2, _ = write_kv(k, v, kn, kn, tables, jnp.zeros(2, jnp.int32))
        np.testing.assert_array_equal(np.asarray(k2[1:]), live)


class TestBlockAllocator:
    def _cache(self, num_blocks=9, bs=4):
        return PagedKVCache(1, 1, 1, block_size=bs,
                            num_blocks=num_blocks, device_init=False)

    def test_ensure_grow_free_roundtrip(self):
        c = self._cache()
        assert c.free_blocks == 8
        assert c.ensure('a', 9)            # 3 blocks of 4
        assert len(c.owned('a')) == 3
        assert c.ensure('a', 9)            # idempotent
        assert len(c.owned('a')) == 3
        assert c.free_blocks == 5
        assert c.free_seq('a') == 3
        assert c.free_blocks == 8
        assert c.audit() == []

    def test_all_or_nothing_on_pressure(self):
        c = self._cache(num_blocks=5)      # 4 usable
        assert c.ensure('a', 12)           # 3 blocks
        assert not c.ensure('b', 8)        # needs 2, only 1 free
        assert c.owned('b') == []          # nothing leaked
        assert c.free_blocks == 1
        assert c.audit() == []

    def test_table_row_pads_with_trash(self):
        c = self._cache()
        c.ensure('a', 6)
        row = c.table_row('a', 5)
        assert row.dtype == np.int32 and row.shape == (5,)
        assert list(row[:2]) == c.owned('a')
        assert all(b == TRASH_BLOCK for b in row[2:])
        with pytest.raises(ValueError):
            c.table_row('a', 1)

    def test_churn_never_leaks_never_aliases(self):
        """Property-style: seeded random admit/append/evict sequences
        keep every allocator invariant at every step."""
        rs = np.random.RandomState(42)
        c = self._cache(num_blocks=17, bs=4)
        live = {}
        for step in range(300):
            op = rs.randint(3)
            if op == 0:                    # admit a new sequence
                sid = f's{step}'
                want = int(rs.randint(1, 20))
                if c.ensure(sid, want):
                    live[sid] = want
            elif op == 1 and live:         # append (grow)
                sid = list(live)[rs.randint(len(live))]
                live_want = live[sid] + int(rs.randint(1, 9))
                if c.ensure(sid, live_want):
                    live[sid] = live_want
            elif op == 2 and live:         # evict
                sid = list(live)[rs.randint(len(live))]
                freed = c.free_seq(sid)
                assert freed == blocks_for(live.pop(sid), 4) \
                    or freed >= 0
            problems = c.audit()
            assert problems == [], f'step {step}: {problems}'
            used = sum(blocks_for(n, 4) for n in live.values())
            assert c.free_blocks == 16 - used
            # frag_report invariants hold at every churn step: the
            # observatory's pool-shape numbers must stay consistent
            # with the allocator truth no matter the interleaving
            fr = c.frag_report()
            assert fr['usable_blocks'] == 16
            assert fr['free_blocks'] == c.free_blocks
            assert fr['owned_blocks'] == used
            assert fr['owned_seqs'] == len(live)
            assert 0 <= fr['largest_free_run'] <= fr['free_blocks']
            if fr['free_blocks']:
                assert fr['free_runs'] >= 1
                assert 0.0 <= fr['frag_frac'] < 1.0
            else:
                assert fr['free_runs'] == 0
                assert fr['frag_frac'] == 0.0
            assert fr['seq_spread_max'] >= fr['seq_spread_mean'] >= \
                (1.0 if live else 0.0)
            assert fr['high_water_blocks'] >= used
        for sid in list(live):
            c.free_seq(sid)
        assert c.free_blocks == 16 and c.audit() == []
        fr = c.frag_report()
        # drained pool: every usable block free, one solid span again
        # would be ideal but free-list order is eviction-dependent —
        # the invariants that MUST hold are exact counts + high water
        assert fr['free_blocks'] == 16 and fr['owned_seqs'] == 0
        assert fr['high_water_blocks'] >= 1


class TestSchedulerHost:
    def _sched(self, num_blocks=33, **kw):
        cache = PagedKVCache(1, 1, 1, block_size=4,
                             num_blocks=num_blocks, device_init=False)
        kw.setdefault('max_slots', 2)
        kw.setdefault('batch_buckets', (1, 2))
        kw.setdefault('bucket_fn', lambda n: 4 if n <= 4 else 8)
        kw.setdefault('max_model_len', 32)
        kw.setdefault('decode_span', 2)
        clock = {'t': 0.0}
        kw.setdefault('now_fn', lambda: clock['t'])
        return ContinuousBatchingScheduler(cache, **kw), cache, clock

    def _req(self, rid, t0=3, new=4, **kw):
        return Request(rid, np.arange(1, t0 + 1), new, **kw)

    def test_admit_caps_at_slots_then_backfills(self):
        s, cache, _ = self._sched()
        for i in range(3):
            s.submit(self._req(f'r{i}'))
        a = s.admit_next()
        b = s.admit_next()
        assert a.rid == 'r0' and b.rid == 'r1'
        assert s.admit_next() is None          # slots full
        a.tokens = [1]
        s.finish(a, 'max_tokens')
        assert cache.owned('r0') == []         # freed on evict
        c = s.admit_next()
        assert c.rid == 'r2'                   # immediate backfill

    def test_plan_pads_to_batch_bucket(self):
        s, cache, _ = self._sched()
        s.submit(self._req('r0'))
        req = s.admit_next()
        req.tokens = [9]
        plan = s.plan()
        assert plan.batch == 1 and plan.requests == [req]
        assert plan.tables.shape == (1, 8)     # 32 / 4
        # the input token stays on the device: the plan names its slot
        assert plan.ctx[0] == 3 and plan.slot[0] == req.slot == 0
        assert plan.sent == [2]                # a whole span of 2
        assert plan.active[0]
        assert plan.limit[0] == 3 + 4 - 1

    def test_preempt_youngest_requeues_and_frees(self):
        s, cache, _ = self._sched()
        s.submit(self._req('r0'))
        s.submit(self._req('r1'))
        a, b = s.admit_next(), s.admit_next()
        a.tokens, b.tokens = [1], [2]
        victim = s.preempt_youngest()
        assert victim is b and b.state == Request.QUEUED
        assert b.tokens == [] and b.ctx == 0 and b.preemptions == 1
        assert cache.owned('r1') == []
        assert s.queue[0] is b                 # head of queue

    def test_deadline_evicts_running_and_queued(self):
        s, cache, clock = self._sched()
        s.submit(self._req('r0', deadline_s=5.0))
        s.submit(self._req('r1', deadline_s=50.0))
        a = s.admit_next()
        a.tokens = [1]
        clock['t'] = 10.0
        breached = s.check_deadlines(clock['t'])
        assert [r.rid for r in breached] == ['r0']
        assert a.state == Request.EVICTED and a.reason == 'deadline'
        assert cache.owned('r0') == []
        assert s.queue and s.queue[0].rid == 'r1'

    def test_infeasible_request_rejected_at_submit(self):
        """A request whose full trajectory can never fit the pool is
        rejected up front — the alternative is an admit -> decode ->
        self-preempt -> re-admit livelock."""
        s, cache, _ = self._sched(num_blocks=4)   # 3 usable blocks
        with pytest.raises(ValueError):
            s.submit(self._req('r0', t0=8, new=9))  # limit 16 -> 4 blk
        # the same shape fits a bigger pool
        s2, _, _ = self._sched(num_blocks=6)
        s2.submit(self._req('r0', t0=8, new=9))

    def test_preemption_rolls_back_token_accounting(self):
        s, cache, _ = self._sched()
        s.submit(self._req('r0'))
        req = s.admit_next()
        req.tokens = [1, 2, 3]
        s.preempt_youngest()
        assert req.discarded_tokens == 3
        assert s.counters['discarded_tokens'] == 3

    def test_reserve_preempts_on_pool_pressure(self):
        # 6 usable blocks: two 3-block prompts fit (each feasible
        # alone: worst case 4 blocks), span growth does not —
        # reservation must preempt the youngest
        s, cache, _ = self._sched(num_blocks=7)
        s.submit(self._req('r0', t0=8, new=9))
        s.submit(self._req('r1', t0=8, new=9))
        a, b = s.admit_next(), s.admit_next()
        a.tokens, b.tokens = [1], [1]
        a.ctx = b.ctx = 8
        preempted = s.reserve_span(8)
        assert preempted and preempted[0] is b
        assert cache.audit() == []
        assert len(cache.owned('r0')) * 4 >= min(8 + 8, a.limit)


class TestPrefillDecodeFactoring:
    def test_generate_routes_through_shared_entry_points(self):
        """The factored prefill()/decode_step() ARE generate's decode
        internals — the serving engine and generate can't drift."""
        from paddle_tpu.models.gpt import GPTForCausalLM
        calls = {'prefill': 0, 'decode': 0}
        orig_p = GPTForCausalLM.prefill
        orig_d = GPTForCausalLM.decode_step

        def count_p(self, *a, **k):
            calls['prefill'] += 1
            return orig_p(self, *a, **k)

        def count_d(self, *a, **k):
            calls['decode'] += 1
            return orig_d(self, *a, **k)

        GPTForCausalLM.prefill = count_p
        GPTForCausalLM.decode_step = count_d
        try:
            m = _tiny_model()
            ids = np.random.RandomState(0).randint(
                0, 128, (1, 5)).astype('int64')
            m.generate(paddle.to_tensor(ids), max_new_tokens=3,
                       temperature=0)
        finally:
            GPTForCausalLM.prefill = orig_p
            GPTForCausalLM.decode_step = orig_d
        assert calls['prefill'] >= 1 and calls['decode'] >= 1

    def test_prefill_decode_step_match_full_forward(self):
        """Driving the factored functions by hand reproduces the
        dense full-forward argmax stream exactly."""
        import jax.numpy as jnp
        m = _tiny_model()
        params, buffers = m.functional_state()
        rs = np.random.RandomState(3)
        ids = rs.randint(0, 128, (2, 4)).astype('int64')
        caches = m.init_decode_caches(2, 10)
        logits, caches = m.prefill(params, buffers,
                                   jnp.asarray(ids),
                                   jnp.zeros((), jnp.int32), caches)
        lg = logits.value if hasattr(logits, 'value') else logits
        toks = [np.asarray(lg)[:, -1].argmax(-1)]
        cur = ids.copy()
        for t in range(2):
            cur = np.concatenate([cur, toks[-1][:, None]], axis=1)
            step_tok = jnp.asarray(toks[-1][:, None])
            logits, caches = m.decode_step(
                params, buffers, step_tok,
                jnp.asarray(4 + t, jnp.int32), caches)
            lg = logits.value if hasattr(logits, 'value') else logits
            toks.append(np.asarray(lg)[:, -1].argmax(-1))
        # dense reference: repeated full forwards
        ref = ids.copy()
        for _ in range(3):
            full = np.asarray(m(paddle.to_tensor(ref)).value)
            ref = np.concatenate(
                [ref, full[:, -1].argmax(-1)[:, None]], axis=1)
        got = np.concatenate([ids] + [t[:, None] for t in toks], 1)
        np.testing.assert_array_equal(got, ref)


class TestServingEngine:
    def test_greedy_bitexact_vs_generate_under_churn(self):
        """Mixed prompt/output lengths forcing admissions, evictions
        and backfill through 4 slots — every request's stream equals
        sequential batch-1 generate bitwise."""
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        rs = np.random.RandomState(0)
        specs = [(int(rs.randint(2, 9)), int(rs.randint(2, 7)))
                 for _ in range(10)]
        reqs = [eng.submit(rs.randint(0, 128, (t0,)).astype('int64'),
                           new) for t0, new in specs]
        rep = eng.run()
        assert rep['audit'] == []
        assert eng.cache.free_blocks == eng.cache.num_blocks - 1
        for req in reqs:
            assert req.state == Request.DONE, (req.rid, req.reason)
            ref = _ref_tokens(m, req.prompt, req.max_new_tokens)
            assert req.tokens == ref, req.rid
        assert rep['ttft_p99_s'] is not None
        assert rep['decoded_tokens'] == sum(n for _, n in specs)

    def test_eos_evicts_and_backfills(self):
        """eos_id: engine truncates exactly where generate's stream
        first emits it, frees the blocks, backfills from the queue."""
        m = _tiny_model()
        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, 128, (4,)).astype('int64')
                   for _ in range(6)]
        refs = [_ref_tokens(m, p, 8) for p in prompts]
        # an eos that actually appears mid-stream in some reference
        flat = [t for r in refs for t in r[:-1]]
        eos = flat[len(flat) // 2]
        eng = ServingEngine(m, _tiny_config(max_slots=2, eos_id=eos,
                                            batch_buckets=(1, 2)))
        reqs = [eng.submit(p, 8) for p in prompts]
        rep = eng.run()
        assert rep['audit'] == []
        truncated = 0
        for req, ref in zip(reqs, refs):
            want = ref[:ref.index(eos) + 1] if eos in ref else ref
            assert req.tokens == want, req.rid
            assert req.state == Request.DONE
            if eos in ref:
                assert req.reason == 'eos'
                truncated += 1
        assert truncated >= 1
        assert eng.cache.free_blocks == eng.cache.num_blocks - 1

    def test_deadline_breach_evicts_with_timeout_event(self):
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        telemetry.reset()
        good = eng.submit(np.arange(1, 5), 3)
        # queued breach: deadline already blown on arrival
        late = eng.submit(np.arange(1, 5), 3, deadline_s=-1.0)
        rep = eng.run()
        assert late.state == Request.EVICTED
        assert late.reason == 'deadline'
        assert good.state == Request.DONE
        evs = telemetry.events('timeout')
        assert any(e.get('rid') == late.rid for e in evs)
        recs = {r['rid']: r for r in rep['requests']}
        assert recs[late.rid]['reason'] == 'deadline'
        assert eng.cache.free_blocks == eng.cache.num_blocks - 1

    def test_watchdog_budget_derives_request_deadlines(self):
        from paddle_tpu.resilience.watchdog import Budget
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config(
            watchdog=Budget(step_s=2.0, first_step_s=10.0)))
        d = eng.request_deadline_s(max_new_tokens=5)
        # prefill allowance + ceil(4/2) decode spans x 2s
        assert d == 10.0 + 2 * 2.0
        req = eng.submit(np.arange(1, 4), 5)
        assert req.deadline_s == d
        # explicit config wins over the derived budget
        eng2 = ServingEngine(_tiny_model(), _tiny_config(
            request_deadline_s=99.0, watchdog=Budget(step_s=2.0)))
        assert eng2.request_deadline_s(5) == 99.0

    def test_live_set_buckets_to_declared_pow2(self):
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        for i in range(3):                    # live 3 -> bucket 4
            eng.submit(np.arange(1, 4), 4)
        eng.run()
        assert "('decode', 4, 2)" in eng.stats()['modules']
        assert not any(s.startswith("('decode', 3")
                       for s in eng.stats()['modules'])

    def test_serve_step_events_and_counters(self):
        m = _tiny_model()
        telemetry.reset()
        eng = ServingEngine(m, _tiny_config())
        eng.submit(np.arange(1, 6), 4)
        eng.run()
        steps = telemetry.events('serve_step')
        assert steps and steps[0]['batch'] in (1, 2, 4)
        done = telemetry.events('serve_request')
        assert done and done[-1]['tokens'] == 4
        assert done[-1]['ttft_s'] is not None
        # a model with no routed layers: the counter is there, at 0
        assert eng._prefills == 1
        assert eng.counts()['moe_kernel_prefills'] == 0

    @pytest.mark.parametrize('exec_tier', [False, True])
    def test_warmup_builds_every_declared_module_up_front(
            self, exec_tier, tmp_path, monkeypatch):
        """warmup() = the deterministic deploy cold-start: afterwards
        NO traffic pattern can trigger a compile — by the engine's own
        count and, with the exec tier armed on an empty directory, by
        the compile cache's: no serialize and no miss after it."""
        from paddle_tpu.core import compile_cache as CC
        if exec_tier:
            monkeypatch.setenv('PADDLE_TPU_COMPILE_CACHE',
                               str(tmp_path / 'cache'))
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        eng.warmup()
        # prompts (4,8) x chunks (1,2) + decode batches (1,2,4)
        assert eng.compile_count == 7
        before = CC.stats()
        if exec_tier:
            assert before.get('serialize_exec', 0) >= 7
        for i in range(5):
            eng.submit(np.arange(1, 3 + i), 3)
        eng.run()
        assert eng.compile_count == 7
        after = CC.stats()
        for k in ('serialize_exec', 'miss_exec'):
            assert after.get(k, 0) == before.get(k, 0), k

    def test_moe_model_rejected(self):
        from paddle_tpu.models.gpt import gpt_moe_tiny
        paddle.seed(0)
        with pytest.raises(ValueError):
            ServingEngine(gpt_moe_tiny(), _tiny_config())

    def test_profile_windows_cover_interventions(self):
        """PR-8 attribution: a profile schedule on the engine closes
        capture windows tagged with exact decode step ids."""
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config(
            profile='every=2,steps=2,start=1,limit=1'))
        assert eng._prof is not None
        eng.submit(np.arange(1, 6), 8)
        eng.submit(np.arange(1, 6), 8)
        eng.run()
        assert eng._prof.windows, 'no capture window closed'
        win = eng._prof.windows[0]
        assert win['step_lo'] >= 1


class TestDispatchInFlight:
    """PR 36: the span of intervention N+1 is planned by counts and
    sent while N is still on the device; the host reads N behind it.
    The tokens are what the synchronous order gave, and whatever
    happens to a request while its span is in flight, the books
    balance."""

    def _load(self, eng, n=9, seed=11, lo=2, hi=9):
        rs = np.random.RandomState(seed)
        return [eng.submit(rs.randint(0, 128, (int(rs.randint(2, 9)),))
                           .astype('int64'), int(rs.randint(lo, hi)))
                for _ in range(n)]

    def _balanced(self, eng, reqs):
        assert eng._in_flight is None
        assert eng.scheduler.audit() == []
        assert eng.cache.free_blocks == eng.cache.num_blocks - 1
        assert eng.decoded_tokens == sum(len(r.tokens) for r in reqs)
        assert sorted(eng.scheduler._free_slots) \
            == list(range(eng.config.max_slots))

    def test_stepped_by_hand_a_spans_tokens_come_one_call_later(self):
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        req = eng.submit(np.arange(1, 6).astype('int64'), 7)
        eng.step()              # prefill, span 1 sent, first token read
        assert len(req.tokens) == 1 and req.dispatched == 2
        assert eng.interventions == 0 and eng._in_flight is not None
        eng.step()              # span 2 sent, span 1 read
        assert len(req.tokens) == 3 and req.dispatched == 2
        assert eng.interventions == 1
        assert eng.decode_dispatches_ahead == 0    # span 1 was not
        eng.step()              # span 3 sent, span 2 read
        assert len(req.tokens) == 5 and req.ctx == 5 + 4
        assert eng.decode_dispatches_ahead == 1
        # span 3 ends the request by count: slot and blocks are back
        # before its tokens are read
        assert req.released and req.slot is None
        assert req.state == Request.RUNNING
        assert eng.cache.owned(req.rid) == []
        assert eng.scheduler.audit() == []
        assert eng.drain() == 2 and req.state == Request.DONE
        assert req.tokens == _ref_tokens(m, req.prompt, 7)
        assert eng.interventions == 3
        assert eng.decode_dispatches_ahead == 2
        self._balanced(eng, [req])
        assert eng.drain() == 0                    # nothing in flight

    def test_ahead_counts_what_it_says(self):
        """Every dispatch but the first of an unbroken run was sent
        while the one before it had not been read; a drain breaks the
        run.  `serve_step` carries the same, event by event."""
        m = _tiny_model()
        telemetry.reset()
        eng = ServingEngine(m, _tiny_config())
        reqs = self._load(eng)
        rep = eng.run()
        n = eng.interventions
        assert n > 3 and eng.decode_dispatches_ahead == n - 1
        assert rep['decode_dispatches_ahead'] == n - 1
        steps = [e for e in telemetry.events('serve_step') if e['span']]
        assert [e['ahead'] for e in steps] == [0] + [1] * (n - 1)
        self._balanced(eng, reqs)
        for req in reqs:
            assert req.tokens == _ref_tokens(m, req.prompt,
                                             req.max_new_tokens)
        # a second run starts with nothing in flight again
        more = self._load(eng, n=3, seed=12)
        eng.run()
        assert eng.decode_dispatches_ahead == eng.interventions - 2
        self._balanced(eng, reqs + more)

    def test_a_freed_slot_and_its_blocks_are_backfilled_at_once(self):
        """Two slots, requests of one span each: the row that ends by
        count in N is replaced in N+1, so every dispatch after the
        first is full (occupancy does not fall)."""
        m = _tiny_model()
        telemetry.reset()
        eng = ServingEngine(m, _tiny_config(max_slots=2,
                                            batch_buckets=(1, 2)))
        rs = np.random.RandomState(2)
        reqs = [eng.submit(rs.randint(0, 128, (4,)).astype('int64'), 3)
                for _ in range(8)]
        eng.run()
        steps = [e for e in telemetry.events('serve_step') if e['span']]
        assert [e['live'] for e in steps] == [2] * 4
        for req in reqs:
            assert req.tokens == _ref_tokens(m, req.prompt, 3)
        self._balanced(eng, reqs)

    def test_preemption_with_a_span_in_flight(self):
        """A pool too small for three rows to grow: the youngest is
        preempted while its span is in flight, those tokens are never
        counted, and it is served again from its prompt."""
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config(
            max_slots=4, batch_buckets=(1, 2, 4), num_blocks=10,
            prompt_buckets=(8,)))
        rs = np.random.RandomState(4)
        reqs = [eng.submit(rs.randint(0, 128, (7,)).astype('int64'), 12)
                for _ in range(3)]
        seen_in_flight = False
        while eng.scheduler.queue or eng.scheduler.running:
            before = eng.scheduler.counters['preempted']
            flight = eng._in_flight
            eng.step()
            if eng.scheduler.counters['preempted'] > before \
                    and flight is not None:
                victim = eng.scheduler.queue[0]
                seen_in_flight |= victim in flight['plan'].requests
                assert victim.tokens == [] and victim.dispatched == 0
            assert eng.scheduler.audit() == []
            assert eng.decoded_tokens == sum(len(r.tokens) for r in reqs)
        eng.drain()
        assert seen_in_flight
        assert eng.scheduler.counters['preempted'] >= 1
        for req in reqs:
            assert req.state == Request.DONE
            assert req.tokens == _ref_tokens(m, req.prompt, 12)
        self._balanced(eng, reqs)

    def test_cancel_with_a_span_in_flight(self):
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        reqs = self._load(eng, n=4, lo=8, hi=12)
        eng.step()
        eng.step()
        assert eng._in_flight is not None
        victim = reqs[1]
        assert victim in eng._in_flight['plan'].requests
        assert eng.cancel(victim.rid)
        # the span in flight was read first, then everything the
        # request had been handed was rolled back
        assert eng._in_flight is None and victim.state == Request.EVICTED
        assert eng.decoded_tokens == sum(
            len(r.tokens) for r in reqs if r is not victim)
        assert eng.scheduler.audit() == []
        eng.run()
        for req in reqs:
            if req is not victim:
                assert req.tokens == _ref_tokens(m, req.prompt,
                                                 req.max_new_tokens)
        assert eng._in_flight is None
        assert eng.cache.free_blocks == eng.cache.num_blocks - 1

    def test_a_deadline_with_a_span_in_flight(self):
        """The request is evicted between two calls while its span is
        on the device: the span's tokens for it are discarded, the
        others' are delivered."""
        clock = {'t': 0.0}
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config(), now_fn=lambda: clock['t'])
        late = eng.submit(np.arange(1, 5).astype('int64'), 12,
                          deadline_s=5.0)
        good = eng.submit(np.arange(2, 7).astype('int64'), 12)
        eng.step()
        eng.step()
        had = len(late.tokens)
        assert late in eng._in_flight['plan'].requests
        clock['t'] = 10.0
        eng.step()              # deadline first, then the span is read
        assert late.state == Request.EVICTED and late.reason == 'deadline'
        assert len(late.tokens) == had and late.dispatched == 0
        assert len(good.tokens) == had + 2
        assert eng.scheduler.audit() == []
        eng.run()
        assert good.tokens == _ref_tokens(m, good.prompt, 12)
        self._balanced(eng, [late, good])

    def test_runs_timeout_with_a_span_in_flight(self):
        """run() cut by its timeout reads what the device was asked
        for before it evicts: every token computed is delivered once."""
        clock = {'t': 0.0}

        def now():
            clock['t'] += 0.01
            return clock['t']

        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config(), now_fn=now)
        reqs = self._load(eng, n=6, lo=10, hi=14)
        rep = eng.run(timeout_s=0.4)
        cut = [r for r in reqs if r.reason == 'engine_timeout']
        assert cut and any(r.tokens for r in cut)
        assert rep['decoded_tokens'] == sum(len(r.tokens) for r in reqs)
        assert rep['audit'] == []
        for req in reqs:
            want = _ref_tokens(m, req.prompt, req.max_new_tokens)
            assert req.tokens == want[:len(req.tokens)]
        self._balanced(eng, reqs)

    def test_eos_is_learnt_a_span_late(self):
        """A row that ends on eos_id cannot be foreseen: the span sent
        before the host knew runs it inactive (nothing after the EOS
        is emitted), and its slot comes back one intervention later
        than the slot of a row that ends at the same token by count."""
        m = _tiny_model()
        prompt = np.arange(3, 8).astype('int64')
        ref = _ref_tokens(m, prompt, 12)
        eos = ref[2]            # the last token of the first span
        assert eos not in ref[:2]
        by_count = ServingEngine(m, _tiny_config())
        counted = by_count.submit(prompt, 3)
        by_count.step()         # first token read; span 1 sent
        assert counted.released and counted.slot is None
        by_count.run()
        assert counted.tokens == ref[:3]

        eng = ServingEngine(m, _tiny_config(eos_id=eos))
        req = eng.submit(prompt, 12)
        eng.step()              # first token read; span 1 sent
        assert not req.released and req.slot == 0
        eng.step()              # span 2 sent BLIND; span 1 read: EOS
        assert req.state == Request.DONE and req.reason == 'eos'
        assert req.tokens == ref[:3] and req.slot is None
        blind = eng._in_flight
        assert req in blind['plan'].requests and blind['plan'].active[0]
        assert eng.scheduler.audit() == []
        assert eng.cache.owned(req.rid) == []
        # the slot is free now: the next admission takes it while the
        # blind span is still to be read
        other = eng.submit(np.arange(9, 13).astype('int64'), 2)
        eng.step()
        assert other.slot == 0 or other.released
        assert not np.asarray(blind['valid']).any()    # the device knew
        assert req.tokens == ref[:3]
        eng.run()
        want = _ref_tokens(m, other.prompt, 2)
        assert other.tokens == (want[:want.index(eos) + 1]
                                if eos in want else want)
        self._balanced(eng, [req, other])

    def test_an_eos_at_the_first_token_is_silenced_on_the_device(self):
        m = _tiny_model()
        prompt = np.arange(3, 8).astype('int64')
        eos = _ref_tokens(m, prompt, 1)[0]
        eng = ServingEngine(m, _tiny_config(eos_id=eos))
        req = eng.submit(prompt, 9)
        eng.step()      # the span was sent before the first token was read
        assert req.tokens == [eos] and req.reason == 'eos'
        valid = np.asarray(eng._in_flight['valid'])
        assert not valid.any()                  # the device knew
        assert eng.drain() == 0
        self._balanced(eng, [req])

    def test_wait_s_asks_for_time_only_with_a_measured_span_in_flight(
            self):
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        assert eng.wait_s() == 0.0              # nothing in flight
        req = eng.submit(np.arange(1, 6).astype('int64'), 12)
        eng.step()
        assert eng.wait_s() == 0.0              # nothing measured yet
        # a span of this shape that took a second, planned in a
        # millisecond: the next intervention can wait
        import collections
        import time
        eng._span_s[(1, 2)] = collections.deque([1.0])
        eng._plan_s.clear()
        eng._plan_s.append(0.001)
        eng._device_free_t = time.monotonic()
        assert 0.9 < eng.wait_s() <= 1.0
        eng.run()
        assert req.tokens == _ref_tokens(m, req.prompt, 12)

    def test_arrivals_over_time_are_served_the_same(self):
        """run() with requests still to come paces itself by wait_s;
        the tokens are the same and nothing is left in flight."""
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        eng.warmup()
        rs = np.random.RandomState(6)
        load = [Request(f'a{i}', rs.randint(0, 128, (5,)).astype('int64'),
                        int(rs.randint(3, 9)), arrival_t=0.01 * i)
                for i in range(8)]
        rep = eng.run(load)
        assert rep['audit'] == []
        for req in load:
            assert req.state == Request.DONE
            assert req.tokens == _ref_tokens(m, req.prompt,
                                             req.max_new_tokens)
        self._balanced(eng, load)


class TestServeConfigAndLoadgen:
    def test_config_resolves_and_roundtrips(self):
        m = _tiny_model()
        c = ServeConfig(max_slots=4, block_size=4)
        c.resolved(m.config)
        assert c.max_model_len == 64
        assert c.batch_buckets == (1, 2, 4)
        assert max(c.prompt_buckets) <= 64
        assert c.num_blocks == 4 * blocks_for(64, 4) + 1
        doc = c.to_dict()
        c2 = ServeConfig.from_json(dict(doc, model='tiny'))
        assert c2.max_slots == 4
        assert tuple(c2.prompt_buckets) == tuple(c.prompt_buckets)

    def test_prompt_over_bucket_set_rejected(self):
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        with pytest.raises(ValueError):
            eng.prompt_bucket(9)              # buckets (4, 8)
        with pytest.raises(ValueError):
            eng.submit(np.arange(40), 4)      # > max_model_len

    def test_poisson_load_is_seed_deterministic(self):
        a = poisson_requests(8, rate_rps=100.0, prompt_lens=(4, 8),
                             new_tokens=(2, 4), vocab_size=64, seed=9)
        b = poisson_requests(8, rate_rps=100.0, prompt_lens=(4, 8),
                             new_tokens=(2, 4), vocab_size=64, seed=9)
        assert [r.arrival_t for r in a] == [r.arrival_t for r in b]
        assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
        assert sorted(r.arrival_t for r in a) == \
            [r.arrival_t for r in a]
        c = poisson_requests(8, rate_rps=100.0, prompt_lens=(4, 8),
                             new_tokens=(2, 4), vocab_size=64, seed=10)
        assert [r.arrival_t for r in a] != [r.arrival_t for r in c]

    def test_engine_honors_arrival_offsets(self):
        m = _tiny_model()
        eng = ServingEngine(m, _tiny_config())
        reqs = poisson_requests(4, rate_rps=1000.0,
                                prompt_lens=(4,), new_tokens=(3,),
                                vocab_size=128, seed=1)
        rep = eng.run(reqs)
        assert all(r.state == Request.DONE for r in reqs)
        # TTFT includes queue wait from the request's own arrival
        for r in rep['requests']:
            assert r['ttft_s'] is not None and r['ttft_s'] >= 0


class TestServingPrecompile:
    def test_bucket_set_precompiles_and_warm_starts(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_COMPILE_CACHE',
                           str(tmp_path / 'cache'))
        from paddle_tpu.core import compile_cache as CC
        m = _tiny_model()
        cfg = _tiny_config(prompt_buckets=(4,), batch_buckets=(1, 2),
                           max_slots=2, prefill_batch=1)
        eng = ServingEngine(m, cfg)
        entries, errors = eng.precompile()
        assert not errors
        # 1 prefill (bucket 4 x chunk 1) + 2 decode batch buckets
        assert len(entries) == 3
        for e in entries:
            assert CC.get('exec', e['fingerprint']) is not None
        # a fresh engine's modules deserialize instead of tracing
        before = CC.stats().get('deserialize_exec', 0)
        eng2 = ServingEngine(m, cfg)
        eng2.submit(np.arange(1, 4), 3)
        eng2.run()
        assert CC.stats().get('deserialize_exec', 0) > before
        ref = _ref_tokens(m, np.arange(1, 4), 3)
        assert eng2.scheduler.finished[0].tokens == ref

    def test_precompile_tool_serve_flag_and_deep_audit(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_COMPILE_CACHE',
                           str(tmp_path / 'cache'))
        cfg = {'model': 'tiny',
               'model_kwargs': {'num_layers': 2, 'hidden_size': 32,
                                'num_heads': 2, 'max_seq_len': 64},
               'block_size': 4, 'max_slots': 2, 'decode_span': 2,
               'prompt_buckets': [4], 'batch_buckets': [2],
               'prefill_batch': 1, 'max_model_len': 16,
               'temperature': 0.0}
        cfg_path = tmp_path / 'serve.json'
        cfg_path.write_text(json.dumps(cfg))
        run_dir = tmp_path / 'run'
        import importlib
        precompile = importlib.import_module('tools.precompile')
        rc = precompile.main([str(run_dir), '--targets', 'none',
                              '--serve', str(cfg_path), '--json'])
        assert rc == 0
        from paddle_tpu.core import compile_cache as CC
        doc = CC.read_precompile_manifest(str(run_dir))
        assert doc['serve_buckets']['prompt_buckets'] == [4]
        assert doc['serve_buckets']['model'] == 'tiny'
        assert len(doc['entries']) == 2       # 1 prefill + 1 decode
        ok, errs = CC.verify_precompile_manifest(str(run_dir))
        assert ok, errs
        check_ckpt = importlib.import_module('tools.check_ckpt')
        # rc 1 = 'no committed checkpoint step yet' (a bare serving
        # deploy dir) — what matters is the deep audit NOT returning
        # exit 6 (precompile manifest invalid)
        assert check_ckpt.main([str(run_dir), '--deep']) in (0, 1)
        # ...and a vanished serving artifact IS caught like any other
        # precompile entry
        fp = doc['entries'][0]['fingerprint']
        os.unlink(os.path.join(str(tmp_path / 'cache'),
                               f'exec-{fp}.ptcc'))
        assert check_ckpt.main([str(run_dir), '--deep']) == 6


class TestServingAnalysis:
    def test_gptserve_is_a_registered_target(self):
        from paddle_tpu.analysis import targets as T
        assert 'gptserve' in T.TARGETS
        import jax
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:1]), ('dp',))
        layer, batch = T.TARGETS['gptserve'](mesh)
        params, buffers, p_sh, b_sh = T.target_state(layer, mesh)
        assert params and batch and len(batch) == 5

    def test_decode_step_lints_zero_recompile_hazards(self):
        """The tpu_lint gate over the declared bucket set: every
        (batch bucket, span) decode module traces with zero
        recompile-hazard (or any HIGH) findings."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu import analysis
        m = _tiny_model()
        cfg = _tiny_config()
        eng = ServingEngine(m, cfg)
        W = eng.scheduler.table_width
        shape = (eng.cache.num_blocks, cfg.block_size,
                 m.config.num_heads,
                 m.config.hidden_size // m.config.num_heads)
        assert eng.cache.pools[0][0].shape == shape
        for S in cfg.batch_buckets:
            fn = eng._decode_build(S, cfg.decode_span)
            pools = tuple(
                jax.ShapeDtypeStruct(shape, jnp.float32)
                for _ in range(m.config.num_layers))
            report = analysis.lint(
                fn, eng._params, eng._buffers, pools, pools,
                jax.ShapeDtypeStruct((cfg.max_slots + 1,), jnp.int32),
                jax.ShapeDtypeStruct((S, W), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32))
            high = [f for f in report if f.severity == 'high']
            assert not high, (S, high)

    def test_audit_layer_runs_eagerly(self):
        from paddle_tpu.serving import DecodeAuditLayer
        m = _tiny_model()
        layer = DecodeAuditLayer(m)
        L, nh, hd = 2, 2, 16
        S, bs, mb = 2, 4, 2
        nb = S * mb + 1
        rs = np.random.RandomState(0)
        out = layer(
            paddle.to_tensor(np.zeros((S, 1), 'int64')),
            paddle.to_tensor(
                rs.randn(L, nb, bs, nh, hd).astype(np.float32)),
            paddle.to_tensor(
                rs.randn(L, nb, bs, nh, hd).astype(np.float32)),
            paddle.to_tensor(
                np.arange(1, 1 + S * mb).reshape(S, mb)
                .astype('int32')),
            paddle.to_tensor(np.array([2, 5], 'int32')))
        logits, nk, nv = out
        assert tuple(np.asarray(
            logits.value if hasattr(logits, 'value')
            else logits).shape) == (S, 1, 128)
        assert np.asarray(nk).shape == (L, nb, bs, nh, hd)
