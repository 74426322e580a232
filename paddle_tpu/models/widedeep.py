"""Wide&Deep and DeepFM — the sparse-embedding recommender models.

Reference analogue: the reference serves these via the brpc parameter
server (fleet/runtime, distributed lookup_table ops): sparse rows live
on PS shards and workers pull/push.  TPU-native substitute (SURVEY.md
§2 item 34): ALL fields share one fused embedding table addressed by
per-field offsets — a single large `gather` the MXU-adjacent memory
system handles natively — and the table shards over the `tp` mesh axis
via VocabParallelEmbedding, so "parameter server" becomes "table rows
spread over chips + XLA-partitioned gather", with the fleet PS API
(init_server/init_worker/...) kept as no-op-compatible surface.
"""
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel import VocabParallelEmbedding
from ..tensor import creation, manipulation, math as pmath

__all__ = ['WideDeep', 'DeepFM']


class _FusedSparseEmbedding(nn.Layer):
    """One table for all sparse fields; ids are per-field local and get
    offset into the fused vocab.  shard=True puts rows on the tp axis."""

    def __init__(self, field_dims, embed_dim, shard=False):
        super().__init__()
        total = int(sum(field_dims))
        self.offsets = np.array(
            [0] + list(np.cumsum(field_dims)[:-1]), dtype='int64')
        if shard:
            self.table = VocabParallelEmbedding(total, embed_dim)
        else:
            self.table = nn.Embedding(total, embed_dim)

    def forward(self, ids):
        """ids [B, F] (field-local) → embeddings [B, F, E]."""
        off = Tensor(self.offsets)
        return self.table(ids + off)


class _PerFieldSparseEmbedding(nn.Layer):
    """Reference-style per-field tables — F separate gathers + stack
    (the shape of the reference's per-slot lookup_table calls,
    fleet/runtime/the_one_ps.py:417).  Kept as the reference the fused
    single-table gather, the default, is compared with
    (tests/test_models.py)."""

    def __init__(self, field_dims, embed_dim):
        super().__init__()
        self.tables = nn.LayerList(
            [nn.Embedding(int(d), embed_dim) for d in field_dims])

    def forward(self, ids):
        """ids [B, F] (field-local) → embeddings [B, F, E]."""
        cols = [t(ids[:, i]) for i, t in enumerate(self.tables)]
        return manipulation.stack(cols, axis=1)


class WideDeep(nn.Layer):
    """wide (1st-order sparse + dense linear) + deep (embeddings→MLP).

    Args:
        sparse_field_dims: vocab size per sparse field.
        dense_dim: number of dense float features (0 to disable).
        embed_dim: deep embedding width.
        hidden: deep MLP widths.
        shard_vocab: shard the fused tables over the tp mesh axis.
        fused_gather: one offset-addressed table per role (default) vs
            reference-style per-field tables (A/B baseline; not
            shardable over tp).
    """

    def __init__(self, sparse_field_dims, dense_dim=0, embed_dim=16,
                 hidden=(64, 32), shard_vocab=False, fused_gather=True):
        super().__init__()
        self.dense_dim = dense_dim
        f = len(sparse_field_dims)
        if not fused_gather and shard_vocab:
            raise ValueError('per-field tables (fused_gather=False) '
                             'do not shard over tp; use the fused '
                             'table for shard_vocab=True')
        if fused_gather:
            self.wide = _FusedSparseEmbedding(sparse_field_dims, 1,
                                              shard=shard_vocab)
            self.deep_emb = _FusedSparseEmbedding(sparse_field_dims,
                                                  embed_dim,
                                                  shard=shard_vocab)
        else:
            self.wide = _PerFieldSparseEmbedding(sparse_field_dims, 1)
            self.deep_emb = _PerFieldSparseEmbedding(sparse_field_dims,
                                                     embed_dim)
        layers = []
        in_dim = f * embed_dim + dense_dim
        for h in hidden:
            layers += [nn.Linear(in_dim, h), nn.ReLU()]
            in_dim = h
        layers.append(nn.Linear(in_dim, 1))
        self.deep = nn.Sequential(*layers)
        self.dense_linear = nn.Linear(dense_dim, 1) if dense_dim else None
        self.bias = self.create_parameter([1], is_bias=True)

    def forward(self, sparse_ids, dense=None):
        B = sparse_ids.shape[0]
        wide = pmath.sum(self.wide(sparse_ids), axis=[1, 2],
                         keepdim=True)[:, :, 0]        # [B, 1]
        emb = self.deep_emb(sparse_ids)                 # [B, F, E]
        deep_in = manipulation.reshape(emb, [B, -1])
        if self.dense_linear is not None and dense is not None:
            wide = wide + self.dense_linear(dense)
            deep_in = manipulation.concat([deep_in, dense], axis=1)
        deep = self.deep(deep_in)                       # [B, 1]
        return wide + deep + self.bias


class DeepFM(nn.Layer):
    """Factorization-machine second-order interactions + deep MLP over
    the same fused embeddings (one gather feeds both)."""

    def __init__(self, sparse_field_dims, dense_dim=0, embed_dim=16,
                 hidden=(64, 32), shard_vocab=False):
        super().__init__()
        self.dense_dim = dense_dim
        f = len(sparse_field_dims)
        self.first_order = _FusedSparseEmbedding(sparse_field_dims, 1,
                                                 shard=shard_vocab)
        self.emb = _FusedSparseEmbedding(sparse_field_dims, embed_dim,
                                         shard=shard_vocab)
        layers = []
        in_dim = f * embed_dim + dense_dim
        for h in hidden:
            layers += [nn.Linear(in_dim, h), nn.ReLU()]
            in_dim = h
        layers.append(nn.Linear(in_dim, 1))
        self.deep = nn.Sequential(*layers)
        self.dense_linear = nn.Linear(dense_dim, 1) if dense_dim else None
        self.bias = self.create_parameter([1], is_bias=True)

    def forward(self, sparse_ids, dense=None):
        B = sparse_ids.shape[0]
        first = pmath.sum(self.first_order(sparse_ids), axis=[1, 2],
                          keepdim=True)[:, :, 0]        # [B, 1]
        e = self.emb(sparse_ids)                        # [B, F, E]
        # FM: 0.5 * ((sum_f e)^2 - sum_f e^2), summed over E
        s = pmath.sum(e, axis=1)                        # [B, E]
        fm = 0.5 * pmath.sum(s * s - pmath.sum(e * e, axis=1),
                             axis=1, keepdim=True)      # [B, 1]
        deep_in = manipulation.reshape(e, [B, -1])
        if self.dense_linear is not None and dense is not None:
            first = first + self.dense_linear(dense)
            deep_in = manipulation.concat([deep_in, dense], axis=1)
        deep = self.deep(deep_in)
        return first + fm + deep + self.bias
