"""Incremental-decoding KV caches.

Reference: /root/reference/python/paddle/nn/layer/transformer.py:151
(Cache/StaticCache), :270 (gen_cache), :566/:893 (layer cache threading),
:1040 (decoder stack).  Parity contract: cached step-by-step decode must
produce EXACTLY the logits of the uncached full-sequence forward, while
doing O(L) (not O(L^2)) attention work per emitted token.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn.layer.transformer import MultiHeadAttention


def _t(a):
    return paddle.to_tensor(np.asarray(a, np.float32))


def _causal_mask(L):
    m = np.where(np.tril(np.ones((L, L), bool)), 0.0, -1e9)
    return _t(m.astype(np.float32))


class TestMHACache:
    def test_gen_cache_shapes(self):
        mha = MultiHeadAttention(16, 4)
        mha.eval()
        x = _t(np.random.randn(2, 5, 16))
        c = mha.gen_cache(x, type=MultiHeadAttention.Cache)
        assert isinstance(c, MultiHeadAttention.Cache)
        assert tuple(c.k.shape) == (2, 4, 0, 4)
        sc = mha.gen_cache(x, x, type=MultiHeadAttention.StaticCache)
        assert isinstance(sc, MultiHeadAttention.StaticCache)
        assert tuple(sc.k.shape) == (2, 4, 5, 4)

    def test_incremental_self_attn_parity(self):
        """Token-by-token cached self-attention == full causal forward."""
        np.random.seed(0)
        paddle.seed(7)
        mha = MultiHeadAttention(16, 4)
        mha.eval()
        x = np.random.randn(2, 6, 16).astype(np.float32)
        full = mha(_t(x), attn_mask=_causal_mask(6))
        full = np.asarray(full.value)

        cache = mha.gen_cache(_t(x), type=MultiHeadAttention.Cache)
        outs = []
        for t in range(6):
            step = _t(x[:, t:t + 1])
            y, cache = mha(step, step, step, cache=cache)
            outs.append(np.asarray(y.value))
        inc = np.concatenate(outs, axis=1)
        np.testing.assert_allclose(inc, full, rtol=2e-5, atol=2e-5)
        # cache grew to the full length
        assert tuple(cache.k.shape) == (2, 4, 6, 4)

    def test_static_cache_cross_attn_parity(self):
        np.random.seed(1)
        paddle.seed(3)
        mha = MultiHeadAttention(16, 4)
        mha.eval()
        q = np.random.randn(2, 3, 16).astype(np.float32)
        mem = np.random.randn(2, 7, 16).astype(np.float32)
        full = np.asarray(mha(_t(q), _t(mem), _t(mem)).value)
        sc = mha.gen_cache(_t(mem), _t(mem),
                           type=MultiHeadAttention.StaticCache)
        y, sc2 = mha(_t(q), cache=sc)
        np.testing.assert_allclose(np.asarray(y.value), full,
                                   rtol=2e-5, atol=2e-5)
        # StaticCache passes through unchanged
        assert sc2.k is sc.k

    def test_cache_seeded_with_prefix(self):
        """UniLM-style: seeding Cache with k/v == processing the prefix."""
        np.random.seed(2)
        mha = MultiHeadAttention(8, 2)
        mha.eval()
        x = np.random.randn(1, 5, 8).astype(np.float32)
        prefix, tail = x[:, :3], x[:, 3:]
        full = np.asarray(mha(_t(x), attn_mask=_causal_mask(5)).value)

        k, v = mha.compute_kv(_t(prefix), _t(prefix))
        cache = mha.gen_cache(k, v, type=MultiHeadAttention.Cache)
        outs = []
        for t in range(2):
            step = _t(tail[:, t:t + 1])
            y, cache = mha(step, step, step, cache=cache)
            outs.append(np.asarray(y.value))
        inc = np.concatenate(outs, axis=1)
        np.testing.assert_allclose(inc, full[:, 3:], rtol=2e-5, atol=2e-5)


class TestDecoderCache:
    def _decoder(self, d=16, nh=4, ff=32, nlayers=2):
        layer = nn.TransformerDecoderLayer(d, nh, ff, dropout=0.0)
        dec = nn.TransformerDecoder(layer, nlayers)
        dec.eval()
        return dec

    def test_decoder_cached_parity(self):
        np.random.seed(3)
        dec = self._decoder()
        tgt = np.random.randn(2, 5, 16).astype(np.float32)
        mem = np.random.randn(2, 7, 16).astype(np.float32)
        full = np.asarray(dec(_t(tgt), _t(mem),
                              tgt_mask=_causal_mask(5)).value)

        cache = dec.gen_cache(_t(mem))
        assert len(cache) == 2
        outs = []
        for t in range(5):
            step = _t(tgt[:, t:t + 1])
            y, cache = dec(step, _t(mem), cache=cache)
            outs.append(np.asarray(y.value))
        inc = np.concatenate(outs, axis=1)
        np.testing.assert_allclose(inc, full, rtol=2e-5, atol=2e-5)

    def test_gen_cache_do_zip(self):
        dec = self._decoder()
        mem = _t(np.random.randn(2, 7, 16))
        z = dec.gen_cache(mem, do_zip=True)
        assert len(z) == 2           # (incrementals, statics)
        assert len(z[0]) == 2        # per layer
        assert isinstance(z[0][0], MultiHeadAttention.Cache)
        assert isinstance(z[1][0], MultiHeadAttention.StaticCache)

    def test_encoder_cached_parity(self):
        """UniLM-style incremental encoding through TransformerEncoder."""
        np.random.seed(4)
        layer = nn.TransformerEncoderLayer(16, 4, 32, dropout=0.0)
        enc = nn.TransformerEncoder(layer, 2)
        enc.eval()
        src = np.random.randn(2, 4, 16).astype(np.float32)
        full = np.asarray(enc(_t(src), src_mask=_causal_mask(4)).value)
        cache = enc.gen_cache(_t(src))
        outs = []
        for t in range(4):
            step = _t(src[:, t:t + 1])
            y, cache = enc(step, cache=cache)
            outs.append(np.asarray(y.value))
        inc = np.concatenate(outs, axis=1)
        np.testing.assert_allclose(inc, full, rtol=2e-5, atol=2e-5)


class TestGPTGenerate:
    def test_greedy_matches_full_forward(self):
        """Static-buffer jit decode == repeated full forwards (greedy)."""
        from paddle_tpu.models.gpt import gpt_tiny
        np.random.seed(5)
        paddle.seed(11)
        m = gpt_tiny(num_layers=2, hidden_size=32, num_heads=2,
                     max_seq_len=32)
        m.eval()
        ids = np.random.randint(0, 128, (2, 4)).astype('int64')
        out = np.asarray(
            m.generate(paddle.to_tensor(ids), max_new_tokens=3,
                       temperature=0).value)
        cur = ids.copy()
        for _ in range(3):
            lg = np.asarray(m(paddle.to_tensor(cur)).value)
            cur = np.concatenate(
                [cur, lg[:, -1].argmax(-1)[:, None]], axis=1)
        np.testing.assert_array_equal(out, cur)

    def test_scan_decode_blocks_token_exact(self):
        """scan_decode_blocks=True (one block body scanned over
        stacked per-layer params — the decode compile-time lever)
        must be token-exact vs the unrolled decode, greedy AND
        sampled."""
        from paddle_tpu.models.gpt import gpt_tiny
        paddle.seed(3)
        m_u = gpt_tiny()
        paddle.seed(3)
        m_s = gpt_tiny(scan_decode_blocks=True)
        m_s.set_state_dict(m_u.state_dict())
        m_u.eval()
        m_s.eval()
        ids = np.random.RandomState(7).randint(
            0, m_u.config.vocab_size, (2, 5)).astype('int64')
        for kw in ({'temperature': 0},
                   {'temperature': 0.8, 'top_k': 8, 'seed': 4}):
            a = np.asarray(m_u.generate(paddle.to_tensor(ids),
                                        max_new_tokens=6, **kw).value)
            b = np.asarray(m_s.generate(paddle.to_tensor(ids),
                                        max_new_tokens=6, **kw).value)
            np.testing.assert_array_equal(a, b)

    def test_scan_decode_ignored_for_moe(self):
        """Heterogeneous stacks (MoE blocks) silently keep the
        unrolled decode — generate must still work."""
        from paddle_tpu.models.gpt import gpt_moe_tiny
        paddle.seed(0)
        m = gpt_moe_tiny(scan_decode_blocks=True)
        m.eval()
        ids = np.zeros((1, 3), 'int64')
        out = np.asarray(m.generate(paddle.to_tensor(ids),
                                    max_new_tokens=4,
                                    temperature=0).value)
        assert out.shape == (1, 7)

    def test_sampled_shape_and_range(self):
        from paddle_tpu.models.gpt import gpt_tiny
        m = gpt_tiny(num_layers=2, hidden_size=32, num_heads=2,
                     max_seq_len=32)
        m.eval()
        ids = np.zeros((1, 3), 'int64')
        out = np.asarray(
            m.generate(paddle.to_tensor(ids), max_new_tokens=5,
                       temperature=0.8, top_k=10, seed=1).value)
        assert out.shape == (1, 8)
        assert (out >= 0).all() and (out < 128).all()

    def test_max_len_guard(self):
        from paddle_tpu.models.gpt import gpt_tiny
        m = gpt_tiny(max_seq_len=8)
        ids = np.zeros((1, 6), 'int64')
        with pytest.raises(ValueError):
            m.generate(paddle.to_tensor(ids), max_new_tokens=4)


class TestBeamSearchWithCache:
    def test_transformer_beam_decode_with_cache(self):
        """BeamSearchDecoder drives a TransformerDecoder cell whose state
        carries (incremental, static) caches — the reference's seq2seq
        decode composition (fluid/layers/rnn.py:866 over
        nn/layer/transformer.py caches)."""
        np.random.seed(6)
        paddle.seed(2)
        d, nh, ff, V, K = 16, 4, 32, 12, 3
        layer = nn.TransformerDecoderLayer(d, nh, ff, dropout=0.0)
        dec = nn.TransformerDecoder(layer, 1)
        dec.eval()
        emb = nn.Embedding(V, d)
        head = nn.Linear(d, V)

        mem = _t(np.random.randn(2, 5, d).astype(np.float32))
        from paddle_tpu.nn.decode import (BeamSearchDecoder,
                                          dynamic_decode)

        tiled_mem = BeamSearchDecoder.tile_beam_merge_with_batch(mem, K)

        class Cell:
            def __call__(self, inputs, states):
                cache = states
                step = paddle.reshape(inputs,
                                      [inputs.shape[0], 1, d])
                out, new_cache = dec(step, tiled_mem, cache=cache)
                return paddle.reshape(out, [out.shape[0], d]), new_cache

        cell = Cell()
        bsd = BeamSearchDecoder(cell, start_token=0, end_token=1,
                                beam_size=K,
                                embedding_fn=emb,
                                output_fn=head)
        # batch-sized caches: initialize() tiles every state leaf to B*K
        init_cache = dec.gen_cache(mem)
        outs, final = dynamic_decode(bsd, inits=init_cache,
                                     max_step_num=4)
        ids = np.asarray(outs.value if hasattr(outs, 'value') else outs)
        assert ids.shape[0] == 2 and ids.shape[2] == K
        assert ids.shape[1] <= 6


class TestHybridCache:
    """`serving/kv_cache.py::HybridCache`'s allocator: one sequence
    holds a slot of state and paged blocks at once, gets both or
    neither, gives both back; its audit sees a sequence that holds one
    without the other."""

    def _cache(self, slots=2, num_blocks=7):
        from paddle_tpu.serving.kv_cache import HybridCache
        return HybridCache(('state', 'kv', 'state'), ((4, 8), (3, 8)), 2,
                           4, block_size=4, num_blocks=num_blocks,
                           slots=slots, max_model_len=64, device_init=False)

    def test_ensure_takes_a_slot_and_the_blocks_or_neither(self):
        cache = self._cache()
        assert cache.ensure('a', 9)            # a slot, 3 blocks
        assert cache.state.owned('a') == [1] and len(cache.owned('a')) == 3
        assert cache.ensure('a', 12) and len(cache.owned('a')) == 3
        # 3 blocks are left: 'b' needs 4, so it takes no slot either
        assert not cache.ensure('b', 13)
        assert cache.state.owned('b') == [] and cache.owned('b') == []
        assert cache.ensure('b', 12) and cache.state.owned('b') == [2]
        # no slot is left: nothing is taken for 'c'
        assert not cache.ensure('c', 1)
        assert cache.free_blocks == cache.free_slots == 0
        # a live sequence that cannot grow keeps what it held
        assert not cache.ensure('a', 16)
        assert cache.state.owned('a') == [1] and len(cache.owned('a')) == 3
        assert cache.audit() == []

    def test_free_gives_both_back_and_counts_both(self):
        """The blocks are the pool's (what the scheduler reads), the
        slots are counted apart."""
        cache = self._cache()
        whole = cache.num_blocks - 1
        assert whole == 6 and cache.free_blocks == whole
        assert cache.free_slots == cache.slots == 2
        cache.ensure('a', 5)
        assert cache.free_blocks == whole - 2 and cache.free_slots == 1
        assert cache.free_seq('a') == 2 + 1
        assert cache.free_blocks == whole and cache.free_slots == 2
        assert cache.owners() == []

    def test_the_audit_sees_one_kind_held_without_the_other(self):
        cache = self._cache()
        cache.ensure('a', 5)
        cache.kv.free_seq('a')
        assert any('a slot and no blocks' in p for p in cache.audit())
        cache.kv.ensure('a', 5)
        cache.state.free_seq('a')
        assert any('blocks and no slot' in p for p in cache.audit())

    def test_where_and_the_plan_rows(self):
        cache = self._cache()
        cache.ensure('a', 6)
        cache.ensure('b', 3)
        blocks, rows = cache.prefill_where(['a', 'b'], 3, 8)
        assert blocks.shape == (3, 2) and list(blocks[1]) == [
            cache.owned('b')[0], 0]
        assert list(rows) == [0, 1, 2]         # the padding row: dropped
        assert cache.state_row((blocks, rows), 1) == 1
