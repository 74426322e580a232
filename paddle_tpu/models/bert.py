"""BERT/ERNIE-style bidirectional encoder + pretraining heads.

Reference analogue: the ERNIE/BERT fleet pretrain benchmarks the
reference runs over NCCL DP (SURVEY.md §3 item 3).  Same TP-layer
construction as GPT (Megatron qkv/proj split on `tp`), but bidirectional
attention (non-causal flash kernel single-chip) plus MLM + NSP heads.
"""
import math

from .. import nn
from ..nn import functional as F
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ..parallel.api import maybe_shard
from ..tensor import linalg, manipulation

__all__ = ['BertConfig', 'BertModel', 'BertForPretraining', 'bert_tiny',
           'bert_base', 'bert_large']


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, max_seq_len=512, type_vocab_size=2,
                 intermediate_size=None, dropout=0.1,
                 layer_norm_epsilon=1e-12, fused_head=False,
                 fused_head_chunks=8):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_seq_len = max_seq_len
        self.type_vocab_size = type_vocab_size
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        # fused MLM head (ops/fused_ce.py): training forward returns
        # the transformed hidden states and loss() fuses the tied
        # decoder matmul + softmax + CE over vocab chunks — the
        # [B·T, V] logits never materialize (single-chip / dp paths;
        # keep off under tp)
        self.fused_head = fused_head
        self.fused_head_chunks = fused_head_chunks


class BertSelfAttention(nn.Layer):
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
        self.drop = nn.Dropout(cfg.dropout)

    def _use_flash(self, T):
        from ..ops.flash_attention import can_use_pallas
        dropout_active = self.training and self.drop.p > 0.0
        return not dropout_active and can_use_pallas(T, T, self.head_dim)

    def forward(self, x, attn_mask=None):
        B, T, H = x.shape
        qkv = self.qkv(x)
        qkv = maybe_shard(qkv, ('dp', None, 'tp'))
        qkv = manipulation.reshape(qkv, [B, T, 3, self.n_head,
                                         self.head_dim])
        q = manipulation.transpose(qkv[:, :, 0], [0, 2, 1, 3])
        k = manipulation.transpose(qkv[:, :, 1], [0, 2, 1, 3])
        v = manipulation.transpose(qkv[:, :, 2], [0, 2, 1, 3])
        if attn_mask is None and self._use_flash(T):
            from ..ops import flash_attention
            from ..core.dispatch import apply
            nh, hd = self.n_head, self.head_dim
            q = manipulation.reshape(q, [B * nh, T, hd])
            k = manipulation.reshape(k, [B * nh, T, hd])
            v = manipulation.reshape(v, [B * nh, T, hd])
            y = apply(lambda qv, kv, vv: flash_attention(
                qv, kv, vv, causal=False), q, k, v,
                op_name='flash_attention')
            y = manipulation.reshape(y, [B, nh, T, hd])
        else:
            q = maybe_shard(q, ('dp', 'tp', None, None))
            k = maybe_shard(k, ('dp', 'tp', None, None))
            v = maybe_shard(v, ('dp', 'tp', None, None))
            att = linalg.matmul(q, k, transpose_y=True)
            att = att * (1.0 / math.sqrt(self.head_dim))
            if attn_mask is not None:
                att = att + attn_mask
            att = F.softmax(att, axis=-1)
            att = self.drop(att)
            y = linalg.matmul(att, v)
        y = manipulation.transpose(y, [0, 2, 1, 3])
        y = manipulation.reshape(y, [B, T, H])
        y = maybe_shard(y, ('dp', None, 'tp'))
        return self.proj(y)


class BertLayer(nn.Layer):
    """post-LN encoder block (original BERT ordering)."""

    def __init__(self, cfg):
        super().__init__()
        self.attn = BertSelfAttention(cfg)
        self.ln1 = nn.LayerNorm(cfg.hidden_size,
                                epsilon=cfg.layer_norm_epsilon)
        self.fc = ColumnParallelLinear(cfg.hidden_size,
                                       cfg.intermediate_size,
                                       gather_output=False)
        self.proj = RowParallelLinear(cfg.intermediate_size,
                                      cfg.hidden_size,
                                      input_is_parallel=True)
        self.ln2 = nn.LayerNorm(cfg.hidden_size,
                                epsilon=cfg.layer_norm_epsilon)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, attn_mask=None):
        x = self.ln1(x + self.drop(self.attn(x, attn_mask)))
        from ..ops.fused_gelu_linear import mlp_gelu
        h = mlp_gelu(x, self.fc, shard_spec=('dp', None, 'tp'))
        h = self.proj(h)
        return self.ln2(x + self.drop(h))


class BertModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.word_emb = VocabParallelEmbedding(config.vocab_size,
                                               config.hidden_size)
        self.pos_emb = nn.Embedding(config.max_seq_len,
                                    config.hidden_size)
        self.type_emb = nn.Embedding(config.type_vocab_size,
                                     config.hidden_size)
        self.ln = nn.LayerNorm(config.hidden_size,
                               epsilon=config.layer_norm_epsilon)
        self.drop = nn.Dropout(config.dropout)
        self.layers = nn.LayerList([BertLayer(config)
                                    for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        B, T = input_ids.shape
        x = self.word_emb(input_ids) + F.embedding_prefix(
            self.pos_emb.weight, T)
        if token_type_ids is not None:
            x = x + self.type_emb(token_type_ids)
        x = self.drop(self.ln(x))
        x = maybe_shard(x, ('dp', None, None))
        for layer in self.layers:
            x = layer(x, attn_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(nn.Layer):
    """MLM (tied decoder) + NSP heads; loss() = mlm_ce + nsp_ce."""

    def __init__(self, config):
        super().__init__()
        self.bert = BertModel(config)
        self.config = config
        self.mlm_transform = nn.Linear(config.hidden_size,
                                       config.hidden_size)
        self.mlm_ln = nn.LayerNorm(config.hidden_size,
                                   epsilon=config.layer_norm_epsilon)
        self.nsp = nn.Linear(config.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None):
        seq, pooled = self.bert(input_ids, token_type_ids)
        h = self.mlm_ln(F.gelu(self.mlm_transform(seq),
                               approximate=True))
        nsp_logits = self.nsp(pooled)
        if self.config.fused_head and self.training:
            # fused MLM head: the tied-decoder matmul happens inside
            # loss() (ops/fused_ce.py) — return the hidden states
            return h, nsp_logits
        logits = linalg.matmul(h, self.bert.word_emb.weight,
                               transpose_y=True)
        logits = maybe_shard(logits, ('dp', None, 'tp'))
        return logits, nsp_logits

    def loss(self, outputs, mlm_labels, nsp_labels=None):
        logits, nsp_logits = outputs
        B, T, D = logits.shape
        # keyed off the SHAPE the forward actually produced, not
        # self.training — a train-forward/eval-loss toggle must not
        # feed hidden states into the unfused CE branch
        if self.config.fused_head and D == self.config.hidden_size \
                and D != self.config.vocab_size:
            from ..core.dispatch import apply as _apply
            from ..ops.fused_ce import fused_linear_cross_entropy
            import jax.numpy as jnp

            def _fce(h, w, lb):
                hh = h.reshape(B * T, D)
                yy = lb.reshape(B * T)
                losses = fused_linear_cross_entropy(
                    hh, w.T, yy,
                    num_chunks=self.config.fused_head_chunks)
                # ignore_index=-100: those labels land in no vocab
                # chunk, so masking the loss zeroes both the value
                # and (through the where) the gradient
                valid = yy != -100
                n = jnp.maximum(jnp.sum(valid), 1)
                return jnp.sum(jnp.where(valid, losses, 0.0)) / n

            mlm = _apply(_fce, logits, self.bert.word_emb.weight,
                         mlm_labels, op_name='fused_mlm_head_ce')
        else:
            lg = manipulation.reshape(logits, [B * T, D])
            lb = manipulation.reshape(mlm_labels, [B * T])
            mlm = F.cross_entropy(lg, lb, ignore_index=-100)
        if nsp_labels is None:
            return mlm
        return mlm + F.cross_entropy(nsp_logits, nsp_labels)


def bert_tiny(**kw):
    kw.setdefault('vocab_size', 128)
    kw.setdefault('hidden_size', 64)
    kw.setdefault('num_layers', 4)
    kw.setdefault('num_heads', 4)
    kw.setdefault('max_seq_len', 128)
    kw.setdefault('dropout', 0.0)
    return BertForPretraining(BertConfig(**kw))


def bert_base(**kw):
    return BertForPretraining(BertConfig(**kw))


def bert_large(**kw):
    kw.setdefault('hidden_size', 1024)
    kw.setdefault('num_layers', 24)
    kw.setdefault('num_heads', 16)
    return BertForPretraining(BertConfig(**kw))
