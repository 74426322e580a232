"""GPT-family causal-transformer — the flagship distributed model.

Reference analogue: the fleet GPT examples driving
python/paddle/distributed/fleet/meta_parallel (mp_layers/pp_layers); the
reference scales it with NCCL TP/PP process groups.  TPU-native design:

- built on fleet.meta_parallel TP layers (ColumnParallelLinear /
  RowParallelLinear / VocabParallelEmbedding) whose PartitionSpecs put
  matmul shards on the `tp` mesh axis — XLA inserts the psum/all-gather
  collectives over ICI;
- sequence-parallel hook: activations between blocks carry a
  P(dp, sp, None) sharding constraint, so long sequences split over the
  `sp` axis (ring attention upgrades this path later);
- eager single-chip: the same code runs unsharded (maybe_shard is the
  identity outside a mesh trace).

Everything under one `jax.jit` train step: no Python control flow
depends on data; dropout threads PRNG keys via the functional-key scope.
"""
import math

import jax
import numpy as np

from .. import nn
from ..nn import functional as F
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ..parallel.api import maybe_shard
from ..tensor import creation, linalg, manipulation, math as pmath

__all__ = ['GPTConfig', 'GPT', 'GPTForCausalLM', 'gpt_tiny', 'gpt_small',
           'gpt_1p3b', 'gpt_moe_tiny']


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, max_seq_len=1024, intermediate_size=None,
                 dropout=0.1, layer_norm_epsilon=1e-5,
                 sequence_parallel=False, initializer_range=0.02,
                 moe_num_experts=0, moe_every=2, moe_top_k=1,
                 moe_capacity_factor=1.25, moe_aux_weight=0.01,
                 fused_head=False, fused_head_chunks=8,
                 striped_sp=False, scan_decode_blocks=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_seq_len = max_seq_len
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        self.sequence_parallel = sequence_parallel
        self.initializer_range = initializer_range
        # MoE (expert parallelism over the 'ep' mesh axis): when
        # moe_num_experts > 0, every moe_every-th block's MLP becomes a
        # SwitchMoE (incubate/moe.py) and loss() adds the load-balance
        # auxiliary term
        self.moe_num_experts = moe_num_experts
        self.moe_every = moe_every
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_aux_weight = moe_aux_weight
        # fused LM head (ops/fused_ce.py): training forward returns
        # the final HIDDEN states and loss() computes linear+softmax+CE
        # chunked over the vocab — the f32 [B·T, V] logits are never
        # materialized.  Single-chip / dp paths; keep off under tp
        # (the head matmul then wants the V-sharded parallel CE).
        self.fused_head = fused_head
        self.fused_head_chunks = fused_head_chunks
        # striped (load-balanced) sequence parallelism: hidden states
        # live in the Striped Attention token order END-TO-END during
        # training (ids/positions striped at embedding, labels
        # shift-then-stripe in the fused loss — the per-token CE mean
        # is permutation-invariant, so loss parity is exact).  Requires
        # sequence_parallel + fused_head; eval/decode stay natural.
        self.striped_sp = striped_sp
        # decode compile-time lever: scan ONE block body over stacked
        # per-layer params inside generate() instead of inlining
        # num_layers copies into the token scan — ~L-times less HLO
        # in the decode module.  CPU
        # measurement (stacks hoisted out of the token body): compile
        # -33%, runtime +70% — CPU materializes each layer's param
        # slice as a copy per token, which TPU's while-loop HBM reads
        # do not; OPT-IN until one A/B inside a serve cell
        # (ROADMAP D3, S8) shows the compile shrink is worth the TPU
        # runtime delta: not measured on the chip.  Token-exact parity with the
        # unrolled path is locked in tests/test_kv_cache.py.  Ignored
        # for heterogeneous stacks (MoE every-k blocks).
        self.scan_decode_blocks = scan_decode_blocks


def _act_spec(cfg):
    """Sharding of [B, T, H] activations between blocks."""
    return ('dp', 'sp' if cfg.sequence_parallel else None, None)


def _striped_sp_now(cfg, training):
    """sp degree iff a forward traced RIGHT NOW should run in the
    striped layout.  ONE gate shared by GPT.forward (which stripes the
    ids/positions) and CausalSelfAttention (which picks the striped
    ring) so the two can never disagree: config opted in, training
    with the fused head (striped hidden states are consumed only by
    the permutation-invariant fused CE loss — eval logits must stay
    natural), dropout inactive (mirrors _ring_mesh: the ring itself is
    gated off under attention dropout), and an sp>1 mesh installed."""
    if not (cfg.striped_sp and cfg.sequence_parallel and cfg.fused_head
            and training):
        return None
    if cfg.dropout > 0.0:
        return None
    from ..distributed import env as _env
    mesh = _env.get_mesh()
    if mesh is None:
        return None
    sp = dict(mesh.shape).get('sp', 1)
    return sp if sp > 1 else None


class CausalSelfAttention(nn.Layer):
    """Multi-head causal attention; qkv column-parallel, output
    row-parallel (Megatron split — one psum per block on TPU ICI)."""

    def __init__(self, cfg):
        super().__init__()
        assert cfg.hidden_size % cfg.num_heads == 0
        self.n_head = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv = ColumnParallelLinear(cfg.hidden_size,
                                        3 * cfg.hidden_size,
                                        gather_output=False)
        self.proj = RowParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                      input_is_parallel=True)
        self.attn_drop = nn.Dropout(cfg.dropout)
        self.resid_drop = nn.Dropout(cfg.dropout)
        self.cfg = cfg

    def _ring_mesh(self):
        """The active mesh, iff sequence-parallel ring attention should
        run: sp axis > 1, config opted in, and dropout inactive."""
        if not self.cfg.sequence_parallel:
            return None
        if self.training and self.attn_drop.p > 0.0:
            return None
        from ..distributed import env as _env
        mesh = _env.get_mesh()
        if mesh is not None and dict(mesh.shape).get('sp', 1) > 1:
            return mesh
        return None

    def _use_flash(self, T):
        """Pallas flash attention on the single chip.  Dropout only
        blocks it while actually active (training mode)."""
        from ..ops.flash_attention import can_use_pallas
        dropout_active = self.training and self.attn_drop.p > 0.0
        return not dropout_active and can_use_pallas(T, T, self.head_dim)

    def _flash_mesh(self, B, T):
        """The active mesh iff flash should run UNDER it (shard_map over
        dp/tp — ops.flash_attention.flash_attention_spmd)."""
        dropout_active = self.training and self.attn_drop.p > 0.0
        if dropout_active:
            return None
        from ..distributed import env as _env
        from ..ops.flash_attention import can_use_pallas_spmd
        mesh = _env.get_mesh()
        if mesh is not None and can_use_pallas_spmd(
                B, self.n_head, T, self.head_dim, mesh):
            return mesh
        return None

    def forward(self, x, cache=None, pos=None):
        B, T, H = x.shape
        # attention needs the full sequence: un-shard T, shard heads on tp
        qkv = self.qkv(x)                       # [B, T, 3H/tp]
        qkv = maybe_shard(qkv, ('dp', None, 'tp'))
        qkv = manipulation.reshape(qkv, [B, T, 3, self.n_head,
                                         self.head_dim])
        q = manipulation.transpose(qkv[:, :, 0], [0, 2, 1, 3])
        k = manipulation.transpose(qkv[:, :, 1], [0, 2, 1, 3])
        v = manipulation.transpose(qkv[:, :, 2], [0, 2, 1, 3])
        if cache is not None and getattr(cache, 'paged', False):
            # paged serving decode (serving/kv_cache.PagedCacheView):
            # ONE query token per sequence, k/v scattered into the
            # sequence's pool blocks through its block table, ragged
            # per-sequence length masking — ops/paged_attention: a
            # Pallas kernel over the pool in place where its gate
            # allows, else the gather reference, bit-exact vs the
            # dense buffer below on shared prefixes.
            if T != 1:
                raise ValueError(
                    'paged cache views decode one token per step; '
                    f'prefill goes through the dense path (got T={T})')
            from ..core.dispatch import apply as _apply
            from ..ops.paged_attention import (paged_attention,
                                               write_kv)

            def paged(kp, vp, tbl, slots, lens, qv, kv, vv):
                kp, vp = write_kv(kp, vp, kv[:, :, 0], vv[:, :, 0],
                                  tbl, slots)
                y = paged_attention(qv[:, :, 0], kp, vp, tbl, lens)
                return y[:, :, None], kp, vp

            y, new_k, new_v = _apply(
                paged, cache.k_pool, cache.v_pool, cache.block_table,
                cache.slots, cache.lens, q, k, v,
                op_name='paged_attention')
            y = manipulation.transpose(y, [0, 2, 1, 3])
            y = manipulation.reshape(y, [B, T, H])
            y = self.proj(y)
            return self.resid_drop(y), cache.updated(
                new_k.value if hasattr(new_k, 'value') else new_k,
                new_v.value if hasattr(new_v, 'value') else new_v)
        if cache is not None:
            # jit-friendly incremental decode: k/v land in a
            # PREALLOCATED [B, nh, Tmax, hd] buffer at traced offset
            # `pos` (lax.dynamic_update_slice) — static shapes, so the
            # whole generate loop compiles to ONE XLA while/scan.  The
            # eager concat-cache equivalent lives in
            # nn.layer.transformer.MultiHeadAttention.Cache.
            from ..core.dispatch import apply as _apply

            def cached(kb, vb, qv, kv, vv, posv):
                import jax
                import jax.numpy as jnp
                p = posv.reshape(()).astype(jnp.int32)
                kb = jax.lax.dynamic_update_slice(
                    kb, kv.astype(kb.dtype), (0, 0, p, 0))
                vb = jax.lax.dynamic_update_slice(
                    vb, vv.astype(vb.dtype), (0, 0, p, 0))
                scores = jnp.einsum('bhqd,bhkd->bhqk', qv, kb) \
                    * (1.0 / math.sqrt(self.head_dim))
                Tmax = kb.shape[2]
                row = p + jnp.arange(T)                  # absolute q pos
                col = jnp.arange(Tmax)
                mask = col[None, :] <= row[:, None]      # causal, static
                scores = jnp.where(mask[None, None], scores, -1e9)
                att = jax.nn.softmax(scores, axis=-1)
                y = jnp.einsum('bhqk,bhkd->bhqd', att, vb)
                return y, kb, vb

            y, new_k, new_v = _apply(cached, cache[0], cache[1], q, k, v,
                                     pos, op_name='cached_attention')
            y = manipulation.transpose(y, [0, 2, 1, 3])
            y = manipulation.reshape(y, [B, T, H])
            y = self.proj(y)
            return self.resid_drop(y), (new_k, new_v)
        ring_mesh = self._ring_mesh()
        if ring_mesh is not None:
            # sequence parallel: K/V rotate around the sp ICI ring, each
            # chip holds T/sp of the sequence (SURVEY.md §2 item 35)
            from ..ops.ring_attention import ring_attention_spmd
            from ..core.dispatch import apply
            nh, hd = self.n_head, self.head_dim
            q = manipulation.reshape(q, [B * nh, T, hd])
            k = manipulation.reshape(k, [B * nh, T, hd])
            v = manipulation.reshape(v, [B * nh, T, hd])
            # same gate as GPT.forward: striped traces get the
            # load-balanced ring over already-striped hidden states
            striped = _striped_sp_now(self.cfg, self.training) is not None
            y = apply(lambda qv, kv, vv: ring_attention_spmd(
                qv, kv, vv, ring_mesh, causal=True, striped=striped,
                pre_striped=striped), q, k, v,
                op_name='ring_attention')
            y = manipulation.reshape(y, [B, nh, T, hd])
        elif self._use_flash(T):
            from ..ops import flash_attention
            from ..core.dispatch import apply
            nh, hd = self.n_head, self.head_dim
            q = manipulation.reshape(q, [B * nh, T, hd])
            k = manipulation.reshape(k, [B * nh, T, hd])
            v = manipulation.reshape(v, [B * nh, T, hd])
            y = apply(lambda qv, kv, vv: flash_attention(
                qv, kv, vv, causal=True), q, k, v,
                op_name='flash_attention')
            y = manipulation.reshape(y, [B, nh, T, hd])
        elif (fmesh := self._flash_mesh(B, T)) is not None:
            # hybrid mesh: the Pallas kernel rides dp/tp via shard_map
            # (batch and heads shard; attention is head-independent)
            from ..ops.flash_attention import flash_attention_spmd
            from ..core.dispatch import apply
            y = apply(lambda qv, kv, vv: flash_attention_spmd(
                qv, kv, vv, fmesh, causal=True), q, k, v,
                op_name='flash_attention_spmd')
        else:
            q = maybe_shard(q, ('dp', 'tp', None, None))
            k = maybe_shard(k, ('dp', 'tp', None, None))
            v = maybe_shard(v, ('dp', 'tp', None, None))
            att = linalg.matmul(q, k, transpose_y=True)  # [B, nh, T, T]
            att = att * (1.0 / math.sqrt(self.head_dim))
            mask = creation.tril(creation.ones([T, T], dtype=att.dtype))
            att = att - (1.0 - mask) * 1e9
            att = F.softmax(att, axis=-1)
            att = self.attn_drop(att)
            y = linalg.matmul(att, v)                    # [B, nh, T, hd]
        y = manipulation.transpose(y, [0, 2, 1, 3])
        y = manipulation.reshape(y, [B, T, H])
        y = maybe_shard(y, ('dp', None, 'tp'))
        y = self.proj(y)                                 # psum over tp
        y = self.resid_drop(y)
        return maybe_shard(y, _act_spec(self.cfg))


class GPTMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.fc = ColumnParallelLinear(cfg.hidden_size,
                                       cfg.intermediate_size,
                                       gather_output=False)
        self.proj = RowParallelLinear(cfg.intermediate_size,
                                      cfg.hidden_size,
                                      input_is_parallel=True)
        self.drop = nn.Dropout(cfg.dropout)
        self.cfg = cfg

    def forward(self, x):
        from ..ops.fused_gelu_linear import mlp_gelu
        h = mlp_gelu(x, self.fc, shard_spec=('dp', None, 'tp'))
        h = self.proj(h)
        h = self.drop(h)
        return maybe_shard(h, _act_spec(self.cfg))


class GPTBlock(nn.Layer):
    def __init__(self, cfg, use_moe=False):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size,
                                epsilon=cfg.layer_norm_epsilon)
        self.attn = CausalSelfAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size,
                                epsilon=cfg.layer_norm_epsilon)
        if use_moe:
            from ..incubate.moe import SwitchMoE
            self.mlp = SwitchMoE(cfg.hidden_size, cfg.intermediate_size,
                                 cfg.moe_num_experts,
                                 top_k=cfg.moe_top_k,
                                 capacity_factor=cfg.moe_capacity_factor)
        else:
            self.mlp = GPTMLP(cfg)
        self.cfg = cfg

    def forward(self, x, cache=None, pos=None):
        # device-side names (jax.named_scope is metadata only): the
        # benchmark's scope readers find `gpt.ln`, `gpt.attn` and
        # `gpt.mlp` in an op's path, forward and backward alike
        new_cache = None
        with jax.named_scope('gpt.ln'):
            h = self.ln1(x)
        with jax.named_scope('gpt.attn'):
            if cache is not None:
                a, new_cache = self.attn(h, cache=cache, pos=pos)
            else:
                a = self.attn(h)
            x = x + a
        with jax.named_scope('gpt.ln'):
            h = self.ln2(x)
        with jax.named_scope('gpt.mlp'):
            x = x + self.mlp(h)
        if cache is not None:
            return x, new_cache
        return maybe_shard(x, _act_spec(self.cfg))


class GPT(nn.Layer):
    """Backbone: embeddings + blocks + final LN → hidden states."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.wte = VocabParallelEmbedding(config.vocab_size,
                                          config.hidden_size)
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size)
        self.drop = nn.Dropout(config.dropout)
        self.blocks = nn.LayerList([
            GPTBlock(config, use_moe=(
                config.moe_num_experts > 0
                and i % config.moe_every == config.moe_every - 1))
            for i in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, caches=None, pos=None):
        B, T = input_ids.shape
        if caches is not None:
            # incremental: absolute positions start at traced offset —
            # a scalar for the lock-step generate() batch, a [B] vector
            # for the serving engine's ragged live set (every sequence
            # at its own depth)
            from ..core.dispatch import apply as _apply
            import jax.numpy as jnp

            def _posv(p):
                if getattr(p, 'ndim', 0) == 0 or p.size == 1:
                    return p.reshape(()).astype(jnp.int64) \
                        + jnp.arange(T, dtype=jnp.int64)
                return p.reshape(-1).astype(jnp.int64)[:, None] \
                    + jnp.arange(T, dtype=jnp.int64)[None, :]

            with jax.named_scope('gpt.embed'):
                posv = _apply(_posv, pos, op_name='pos_offset')
                x = self.wte(input_ids) + self.wpe(posv)
                x = self.drop(x)
            new_caches = []
            for blk, c in zip(self.blocks, caches):
                x, nc = blk(x, cache=c, pos=pos)
                new_caches.append(nc)
            with jax.named_scope('gpt.ln'):
                return self.ln_f(x), new_caches
        sp = _striped_sp_now(self.config, self.training)
        # what THIS forward actually produced — loss() consults the
        # record rather than re-deriving from live mode/mesh state,
        # so a train-forward/eval-loss split cannot mispair layouts
        self._last_striped = sp
        with jax.named_scope('gpt.embed'):
            x = self._embed(input_ids, T, sp)
        x = self.drop(x)
        x = maybe_shard(x, _act_spec(self.config))
        for blk in self.blocks:
            x = blk(x)
        with jax.named_scope('gpt.ln'):
            return self.ln_f(x)

    def _embed(self, input_ids, T, sp):
        """Token plus position rows of the uncached forward."""
        if sp is not None:
            # end-to-end striped layout: ids and the position rows
            # enter in stripe order; every block then runs the
            # load-balanced striped ring with NO per-layer relayout
            from ..core.dispatch import apply as _apply
            from ..ops.ring_attention import stripe_tokens
            input_ids = _apply(
                lambda v: stripe_tokens(v, sp, axis=1), input_ids,
                op_name='stripe_ids')
            pos_rows = F.embedding_prefix(self.wpe.weight, T)
            pos_rows = _apply(
                lambda v: stripe_tokens(v, sp, axis=0), pos_rows,
                op_name='stripe_pos')
            return self.wte(input_ids) + pos_rows
        return self.wte(input_ids) + F.embedding_prefix(
            self.wpe.weight, T)


class GPTForCausalLM(nn.Layer):
    """GPT + tied LM head; forward returns logits, loss() the LM loss."""

    def __init__(self, config):
        super().__init__()
        self.gpt = GPT(config)
        self.config = config

    def forward(self, input_ids, caches=None, pos=None):
        if caches is not None:
            h, new_caches = self.gpt(input_ids, caches=caches, pos=pos)
            logits = linalg.matmul(h, self.gpt.wte.weight,
                                   transpose_y=True)
            return logits, new_caches
        h = self.gpt(input_ids)
        if self.config.fused_head and self.training:
            # fused-head training: the head matmul happens inside
            # loss() (ops/fused_ce.py) — return the hidden states
            return h
        # tied head: h @ wte.T — logits [B, T, V/tp-sharded]
        logits = linalg.matmul(h, self.gpt.wte.weight, transpose_y=True)
        return maybe_shard(logits, ('dp', None, 'tp'))

    def loss(self, logits, labels, aux_losses=None):
        """Causal LM loss: shift-by-one cross entropy (+ the MoE
        load-balance auxiliary term when experts are routed).

        `aux_losses`: explicit list of per-block MoE aux losses (from
        `SwitchMoE.forward(..., return_aux=True)`).  REQUIRED when
        this loss is compiled in a different trace than the forward —
        the fallback reads each block's `.aux_loss` attribute, which
        is only valid within the same trace (it raises a clear error
        otherwise instead of leaking a tracer).

        With `config.fused_head` the training forward returns HIDDEN
        states [B, T, H] and the linear+softmax+CE fuse here via
        ops/fused_ce.py — no [B·T, V] logits tensor exists."""
        B, T, D = logits.shape
        # keyed off the SHAPE the forward actually produced, not
        # self.training — a train-forward/eval-loss toggle must not
        # feed hidden states into the unfused CE branch
        if self.config.fused_head and D == self.config.hidden_size \
                and D != self.config.vocab_size:
            from ..core.dispatch import apply as _apply
            from ..ops.fused_ce import fused_linear_cross_entropy
            # layout the forward ACTUALLY produced (recorded at trace
            # time), not a re-derivation from live mode/mesh state
            sp = getattr(self.gpt, '_last_striped', None)

            if sp is not None:
                from ..ops.ring_attention import stripe_tokens

                def _fce(h, w, lb):
                    # hidden states arrive STRIPED; labels are natural
                    # ids: shift in natural order, mark the last
                    # position invalid, then stripe — the masked mean
                    # over B*(T-1) tokens equals the natural-order loss
                    # exactly (the CE mean is permutation-invariant)
                    import jax.numpy as jnp
                    nxt = jnp.concatenate(
                        [lb[:, 1:], jnp.zeros((B, 1), lb.dtype)], 1)
                    valid = jnp.concatenate(
                        [jnp.ones((B, T - 1), bool),
                         jnp.zeros((B, 1), bool)], 1)
                    nxt = stripe_tokens(nxt, sp, axis=1)
                    valid = stripe_tokens(valid, sp, axis=1)
                    hh = h.reshape(B * T, D)
                    losses = fused_linear_cross_entropy(
                        hh, w.T, nxt.reshape(B * T),
                        num_chunks=self.config.fused_head_chunks)
                    vv = valid.reshape(B * T).astype(losses.dtype)
                    return jnp.sum(losses * vv) / jnp.sum(vv)
            else:
                def _fce(h, w, lb):
                    hh = h[:, :-1, :].reshape(B * (T - 1), D)
                    yy = lb[:, 1:].reshape(B * (T - 1))
                    losses = fused_linear_cross_entropy(
                        hh, w.T, yy,
                        num_chunks=self.config.fused_head_chunks)
                    return losses.mean()

            out = _apply(_fce, logits, self.gpt.wte.weight,
                         labels, op_name='fused_lm_head_ce')
        else:
            lg = manipulation.reshape(logits[:, :-1, :],
                                      [B * (T - 1), D])
            lb = manipulation.reshape(labels[:, 1:], [B * (T - 1)])
            out = F.cross_entropy(lg, lb)
        if self.config.moe_num_experts > 0:
            if aux_losses is not None:
                aux = list(aux_losses)
            else:
                aux = [blk.mlp.aux_loss for blk in self.gpt.blocks
                       if getattr(blk.mlp, 'aux_loss', None)
                       is not None]
            if aux:
                total = aux[0]
                for a in aux[1:]:
                    total = total + a
                out = out + self.config.moe_aux_weight * \
                    (total / float(len(aux)))
        return out

    def init_decode_caches(self, batch_size, max_len, dtype=None):
        """Per-layer dense KV buffers ``[B, nh, max_len, hd]`` for the
        cached forward — what ``generate`` preallocates internally.
        The serving engine allocates prefill-sized ones (rounded up to
        its KV block size) and scatters them into the paged pool."""
        import jax.numpy as jnp
        cfg = self.config
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        dtype = dtype or jnp.float32
        shape = (int(batch_size), nh, int(max_len), hd)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                for _ in range(cfg.num_layers)]

    def prefill(self, params, buffers, ids, pos, caches):
        """Pure cached forward over a (padded) prompt: every position's
        k/v lands in ``caches`` starting at ``pos``; returns
        ``(logits, new_caches)``.  Safe inside jit — ``generate`` and
        the serving engine (``serving/engine.py``) both run their
        prefill through here, so the two can never drift.

        ``caches`` is a list of per-layer dense ``(k, v)`` buffers
        (``init_decode_caches``) or paged views
        (``serving.kv_cache.PagedCacheView``, decode only);
        ``pos`` is a traced scalar (lock-step batch) or a ``[B]``
        vector (ragged serving batch) of absolute start positions."""
        from ..jit import functional_call
        (logits, new_caches), _ = functional_call(
            self, params, buffers, (ids,),
            kwargs={'caches': caches, 'pos': pos}, training=False)
        return logits, new_caches

    def decode_step(self, params, buffers, tok, pos, caches):
        """One incremental decode step: ``tok`` is ``[B, 1]`` (the
        previous step's sampled token), ``pos`` its absolute
        position(s).  Same pure cached forward as :meth:`prefill` —
        factored apart so callers (generate's token scan, the serving
        engine's continuous-batching step) name what they mean."""
        return self.prefill(params, buffers, tok, pos, caches)

    def generate(self, input_ids, max_new_tokens, temperature=1.0,
                 top_k=None, seed=0):
        """Autoregressive decode, ONE compiled XLA module.

        Prefill runs the prompt through the cached forward (writing every
        prompt position's k/v into the preallocated buffers), then a
        `lax.scan` emits max_new_tokens tokens with O(1) attention work
        per step — no per-step retracing, no growing shapes.  temperature
        0 = greedy argmax; otherwise softmax sampling (optionally top-k
        truncated).  Returns [B, T0 + max_new_tokens] token ids.

        Prompt lengths are BUCKETED to the next power of two: the
        prompt is right-padded to the bucket, the true length rides as
        a traced scalar (prefill samples at row T0-1; decode overwrites
        the padded k/v slots before the causal mask can expose them),
        so the compiled-module set stays finite across arbitrary
        prompt lengths — the serving-bucket precursor.  Token streams
        are bit-identical to the unbucketed decode (the padded tail is
        masked to exact zeros).  Modules are keyed through the shared
        ``core.compile_cache`` fingerprint and persisted as
        ``jax.export`` artifacts, so a fresh process (restart, serving
        cold-start) deserializes instead of re-tracing; see
        ``precompile_decode`` for the export-time AOT path.

        The reference decodes through fluid's BeamSearchDecoder host loop
        (fluid/layers/rnn.py:1581); this is the TPU-native equivalent of
        its cache mechanism (nn/layer/transformer.py:151).
        """
        import jax
        import jax.numpy as jnp
        from ..core import compile_cache as _cc

        cfg = self.config
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int64)
        B, T0 = ids.shape
        if int(max_new_tokens) < 1:
            return Tensor(ids)
        if T0 + int(max_new_tokens) > cfg.max_seq_len:
            raise ValueError(
                f'prompt+new tokens {T0 + int(max_new_tokens)} exceeds '
                f'max_seq_len {cfg.max_seq_len}')
        if not hasattr(self, '_gen_cache'):
            self._gen_cache = {}
        # the serving hot path keys on the CHEAP signature (bucketed
        # prompt, not exact length); the fingerprint/closure build in
        # _decode_program runs only on a module-cache miss
        P = self._decode_bucket(T0, int(max_new_tokens))
        greedy = temperature == 0 or temperature is None
        sig = (B, P, int(max_new_tokens), greedy,
               float(temperature or 0.0), top_k)
        params, buffers = self.functional_state()
        ids_p = jnp.pad(ids, ((0, 0), (0, P - T0)))
        t0v = jnp.asarray(T0, jnp.int32)
        key = jax.random.PRNGKey(seed)
        jitted = self._gen_cache.get(sig)
        if jitted is None:
            gen_fn, fp, _ck, _P = self._decode_program(
                B, T0, int(max_new_tokens), temperature, top_k,
                params=params)
            if fp is not None:
                jitted = _cc.lookup_executable(fp, name='GPT.generate')
                if jitted is not None:
                    # aval drift (x64 flip etc.) degrades to a fresh
                    # jit instead of crashing the serve path
                    jitted = _cc._with_fallback(
                        jitted, jax.jit(gen_fn), name='GPT.generate')
            if jitted is None:
                # export-primary: ONE trace serves both the persistent
                # artifact and this process's executable (plain jax.jit
                # when the cache is off or the trace is unexportable)
                jitted = _cc.export_jit(
                    gen_fn, (params, buffers, ids_p, t0v, key), fp=fp,
                    name='GPT.generate')
            self._gen_cache[sig] = jitted
        new = jitted(params, buffers, ids_p, t0v, key)
        return Tensor(jnp.concatenate([ids, new], axis=1))

    def precompile_decode(self, batch_size, prompt_len, max_new_tokens,
                          temperature=1.0, top_k=None):
        """AOT warm start for one decode bucket: build, export and
        persist the decode module `generate` would compile for this
        (batch, bucketed prompt, new tokens, sampling) signature —
        without running it.  Returns (fingerprint, prompt_bucket).
        ``tools/precompile.py`` drives this over the declared serving
        bucket set at export time; a later worker's ``generate``
        deserializes the artifact instead of re-tracing."""
        import jax
        import jax.numpy as jnp
        from ..core import compile_cache as _cc
        if prompt_len + int(max_new_tokens) > self.config.max_seq_len:
            raise ValueError(
                f'prompt+new tokens {prompt_len + int(max_new_tokens)} '
                f'exceeds max_seq_len {self.config.max_seq_len}')
        gen_fn, fp, _ck, P = self._decode_program(
            int(batch_size), int(prompt_len), int(max_new_tokens),
            temperature, top_k)
        if fp is None or not _cc.enabled():
            return fp, P
        if _cc.get('exec', fp, name='precompile_decode') is None:
            params, buffers = self.functional_state()
            example = (params, buffers,
                       jnp.zeros((int(batch_size), P), jnp.int64),
                       jnp.asarray(P, jnp.int32), jax.random.PRNGKey(0))
            _cc.store_executable(fp, jax.jit(gen_fn), example,
                                 name='GPT.generate', aot_compile=True)
        return fp, P

    def _decode_bucket(self, T0, max_new_tokens):
        """Prompt bucket for one decode signature: next power of two
        (capped so bucket + new tokens fit max_seq_len).  MoE configs
        are exempt — padded garbage tokens would compete with real
        ones for expert capacity in prefill."""
        from ..core import compile_cache as _cc
        cfg = self.config
        if cfg.moe_num_experts > 0:
            return T0
        return _cc.bucket_pow2(T0, cap=cfg.max_seq_len - max_new_tokens)

    def _decode_program(self, B, T0, max_new_tokens, temperature,
                        top_k, params=None):
        """Build the decode function + its shared cache fingerprint for
        one signature.  Returns (gen_fn, fingerprint, module_key,
        prompt_bucket); gen_fn(params, buffers, ids[B, bucket],
        t0_scalar, key) -> new tokens [B, max_new_tokens].  `params`
        (shapes only are read) saves callers that already hold the
        functional state a second full tree walk."""
        import jax
        import jax.numpy as jnp
        from ..core import compile_cache as _cc
        from ..jit import functional_call

        cfg = self.config
        P = self._decode_bucket(T0, max_new_tokens)
        Tmax = P + max_new_tokens
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        L = cfg.num_layers
        model = self
        greedy = temperature == 0 or temperature is None

        # shared key discipline (ops/sampling): the token at absolute
        # position `pos` of row `r` is drawn with
        # fold_in(fold_in(base, pos), r) — a pure function of (seed,
        # position, row), NOT of the split-chain history.  The paged
        # serving engine derives per-request keys under the same rule
        # (row 0), which is what makes sampled engine-vs-generate
        # parity and mid-stream retry replay bit-exact.
        from ..ops.sampling import sample_rows as _sample_rows

        def sample(logits, base, pos):
            return _sample_rows(logits, base, pos, temperature, top_k)

        # scan-over-layers decode: ONE block body over stacked
        # per-layer params — ~L-times less HLO in the decode module
        # than inlining every block into the token scan.  Needs a
        # homogeneous stack (no MoE blocks).
        use_scan = (cfg.scan_decode_blocks and L > 1
                    and cfg.moe_num_experts == 0)
        blocks_prefix = 'gpt.blocks.'
        block0 = self.gpt.blocks[0]

        def _sub(tree, prefix):
            return {k[len(prefix):]: v for k, v in tree.items()
                    if k.startswith(prefix)}

        def _stacked(tree):
            """{'0.attn.qkv.weight': v, ...} → {'attn.qkv.weight':
            [L, ...]} — per-layer leaves stacked for lax.scan."""
            per = {}
            for k, v in _sub(tree, blocks_prefix).items():
                i, sub = k.split('.', 1)
                per.setdefault(sub, [None] * L)[int(i)] = v
            return {k: jnp.stack(vs) for k, vs in per.items()}

        def _scan_blocks(x, stacked_p, stacked_b, k_all, v_all, p):
            """Run the homogeneous block stack as one lax.scan; caches
            ride as [L, B, nh, Tmax, hd] xs/ys."""
            def layer_body(xc, per_layer):
                lp, lb, kc, vc = per_layer
                (xc, (nk, nv)), _ = functional_call(
                    block0, lp, lb, (xc,),
                    kwargs={'cache': (kc, vc), 'pos': p},
                    training=False)
                return xc, (nk, nv)
            x, (nk_all, nv_all) = jax.lax.scan(
                layer_body, x, (stacked_p, stacked_b, k_all, v_all))
            return x, nk_all, nv_all

        def _scan_step(state, ids_t, p, cache):
            """Embeddings → scanned blocks → ln_f → tied head, built
            from the same sublayers the unrolled path runs (dropout is
            identity in eval).  `state` carries the per-layer stacks
            computed ONCE per generate call (stacking in here would
            re-emit L-way stacks into every token-scan body) plus only
            the NON-block subtrees — threading the full params dict
            through would keep a second unused copy of every block
            weight live in the module.  (The stacks themselves still
            double block-weight HBM versus the unrolled form for the
            duration of the call — the price of the smaller module.)"""
            params, buffers, stacked_p, stacked_b = state
            k_all, v_all = cache
            T = ids_t.shape[1]
            posv = p.reshape(()).astype(jnp.int64) \
                + jnp.arange(T, dtype=jnp.int64)
            emb, _ = functional_call(
                model.gpt.wte, _sub(params, 'gpt.wte.'),
                _sub(buffers, 'gpt.wte.'), (ids_t,), training=False)
            pe, _ = functional_call(
                model.gpt.wpe, _sub(params, 'gpt.wpe.'),
                _sub(buffers, 'gpt.wpe.'), (posv,), training=False)
            x, nk_all, nv_all = _scan_blocks(
                emb + pe, stacked_p, stacked_b, k_all, v_all, p)
            h, _ = functional_call(
                model.gpt.ln_f, _sub(params, 'gpt.ln_f.'),
                _sub(buffers, 'gpt.ln_f.'), (x,), training=False)
            logits = jnp.einsum('bth,vh->btv', h,
                                params['gpt.wte.weight'])
            return logits, (nk_all, nv_all)

        def _unrolled_prefill(state, ids_t, p, caches):
            # the factored serving-shared entry points: generate's
            # prefill and token steps run the SAME pure cached forward
            # the serving engine calls (prefill()/decode_step()), so
            # batch-1 generate and the continuous-batching engine can
            # never drift apart numerically
            return model.prefill(*state, ids_t, p, caches)

        def _unrolled_decode(state, tok_t, p, caches):
            return model.decode_step(*state, tok_t, p, caches)

        def _make_gen(prepare, step, init_cache, decode=None):
            """One decode loop for both block forms: prefill (padded to
            the bucket, true prompt length `t0` traced), sample at row
            t0-1, then a token lax.scan over `step` starting at
            position t0.  Bucketing stays bit-exact: rows < t0 only
            attend real columns, the garbage k/v the padded prefill
            rows wrote at t0..P-1 is overwritten by each decoded
            token's slot BEFORE the causal mask (col <= row) can ever
            expose it, and the masked softmax tail underflows to exact
            zeros."""
            decode = decode or step

            def gen(params, buffers, ids, t0, key):
                state = prepare(params, buffers)
                logits, cache = step(state, ids,
                                     jnp.zeros((), jnp.int32),
                                     init_cache())
                # key is the per-call BASE; each sampled token derives
                # its own key from its absolute position (t0-1 for the
                # prefill sample, p for each scan step)
                tok = sample(jnp.take(logits, t0 - 1, axis=1),
                             key, t0 - 1)  # [B]

                def body(carry, _):
                    tok, p, cache = carry
                    logits, cache = decode(state, tok[:, None], p,
                                           cache)
                    ntok = sample(logits[:, -1], key, p)
                    return (ntok, p + 1, cache), tok

                (last, _, _), toks = jax.lax.scan(
                    body, (tok, t0, cache),
                    None, length=max_new_tokens - 1)
                return jnp.concatenate(
                    [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
            return gen

        def _nonblock(tree):
            return {k: v for k, v in tree.items()
                    if not k.startswith(blocks_prefix)}

        if use_scan:
            gen_fn = _make_gen(
                lambda p, b: (_nonblock(p), _nonblock(b),
                              _stacked(p), _stacked(b)),
                _scan_step,
                lambda: (jnp.zeros((L, B, nh, Tmax, hd), jnp.float32),
                         jnp.zeros((L, B, nh, Tmax, hd), jnp.float32)))
        else:
            gen_fn = _make_gen(
                lambda p, b: (p, b),
                _unrolled_prefill,
                lambda: model.init_decode_caches(B, Tmax),
                decode=_unrolled_decode)

        # the decode signature keys the module: bucketed prompt P (not
        # T0), so every prompt length in a bucket reuses ONE compiled
        # module, in-process and across processes
        if params is None:
            params, _ = self.functional_state()
        pspec = tuple(sorted((n, tuple(v.shape), str(v.dtype))
                             for n, v in params.items()))
        fp = _cc.fingerprint(
            'gpt-decode', config=tuple(sorted(vars(cfg).items())),
            params=pspec, batch=B, prompt_bucket=P, new=max_new_tokens,
            sampling=(greedy, float(temperature or 0.0), top_k),
            scan=use_scan,
            # sampled modules draw keys per absolute position (the
            # ops/sampling discipline) — a pre-discipline artifact
            # would replay the old split-chain stream, so the marker
            # bumps SAMPLED fingerprints only (greedy HLO never reads
            # the key; those artifacts stay valid and cache-hit)
            **({} if greedy else {'key_discipline': 'per-pos-row'}),
            # prompt-ids aval dtype follows the x64 setting — a module
            # exported under one setting must not be handed the other
            ids_dtype=str(jnp.asarray(0, jnp.int64).dtype))
        ck = fp or ('gen', B, P, max_new_tokens, greedy,
                    float(temperature or 0.0), top_k, use_scan)
        return gen_fn, fp, ck, P

    def as_pipeline_module(self, num_stages, mesh):
        """Adapter for the 1F1B pipeline engine (parallel.pipeline_1f1b):
        repacks parameters into shared/stage-stacked pytrees and exposes
        pure stage functions.  See models/gpt_pipe.py."""
        from .gpt_pipe import GPTPipeModule
        return GPTPipeModule(self, num_stages, mesh)


def gpt_tiny(**kw):
    """4-layer toy config for tests/dryruns."""
    kw.setdefault('vocab_size', 128)
    kw.setdefault('hidden_size', 64)
    kw.setdefault('num_layers', 4)
    kw.setdefault('num_heads', 4)
    kw.setdefault('max_seq_len', 128)
    kw.setdefault('dropout', 0.0)
    return GPTForCausalLM(GPTConfig(**kw))


def gpt_moe_tiny(**kw):
    """gpt_tiny with routed experts on alternating blocks — the ep-axis
    dryrun/test config."""
    kw.setdefault('moe_num_experts', 4)
    kw.setdefault('moe_top_k', 1)
    return gpt_tiny(**kw)


def gpt_small(**kw):
    """GPT-2 small (117M)."""
    kw.setdefault('hidden_size', 768)
    kw.setdefault('num_layers', 12)
    kw.setdefault('num_heads', 12)
    return GPTForCausalLM(GPTConfig(**kw))


def gpt_1p3b(**kw):
    """GPT-3 XL-ish 1.3B — the hybrid-parallel benchmark config
    (SURVEY.md §3 item 4)."""
    kw.setdefault('hidden_size', 2048)
    kw.setdefault('num_layers', 24)
    kw.setdefault('num_heads', 16)
    kw.setdefault('max_seq_len', 2048)
    return GPTForCausalLM(GPTConfig(**kw))
