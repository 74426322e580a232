"""Paged KV cache: fixed-size blocks in one preallocated pool.

Two kinds of per-sequence memory live here, behind one set of calls
(`ensure`, `free_seq`, `owned`, `owners`, `table_row`, `audit`, the
counts of free and total; on the device side `arrays`, `set_arrays`,
`prefill_caches`, `store_prefill`, `decode_views`, `arrays_of`), so
that the scheduler and the engine keep one code path: the paged KV
pool below, blocks that grow with a sequence's length, and
`RecurrentStateCache` at the end of this file, one state of fixed size
a sequence.  The model says which it needs (`model.serving_state`).

The serving engine never allocates per-sequence KV buffers.  Instead
each layer owns ONE device pool ``[num_blocks, block_size, num_heads,
head_dim]`` allocated once at engine construction, and every live
sequence owns an ordered list of pool blocks (its *block table*).
Positions lie outside heads because that is the order every user of a
pool wants: `write_kv` scatters one ``[num_heads, head_dim]`` row at a
(block, position), the decode modules' ``lax.scan`` carries the pools
that way, and `paged_decode` copies a block as it lies.  Stated in
that order a decode module's arguments, carry and results share one
layout, and XLA copies no pool round the scan (PERF.md section 6,
PR 28; ``ops/paged_attention.py``).
Admission allocates blocks, eviction frees them — memory churn is a
host-side free-list operation, never a device reallocation, so the
compiled decode step's shapes never change (the zero-recompile
property the whole serving surface is built on).

Block 0 is reserved as the **trash block**: inactive batch slots in a
compiled decode step point their tables at it so their (masked,
ignored) writes land somewhere harmless.  The allocator never hands
out block 0, and ``audit()`` proves the invariants the churn tests
lean on: a block is owned by at most one sequence, owned and free
sets never intersect, and nothing leaks.

Sharding: pools carry their heads on the ``tp`` mesh axis
(``ops.paged_attention.POOL_SPEC``) — the same Megatron head split as
the attention weights, applied by the engine's compiled steps via
``maybe_shard`` when a mesh is installed.
"""
import jax
import jax.numpy as jnp
import numpy as np

__all__ = ['PagedKVCache', 'PagedCacheView', 'RecurrentStateCache',
           'RecurrentStateView', 'TRASH_BLOCK', 'blocks_for']

TRASH_BLOCK = 0


def blocks_for(num_positions, block_size):
    """Blocks needed to hold `num_positions` cache slots."""
    return -(-int(num_positions) // int(block_size))


@jax.tree_util.register_pytree_node_class
class PagedCacheView:
    """One layer's paged cache as seen by a compiled decode step.

    A pytree of (k_pool, v_pool, block_table, slots, lens):

    - ``k_pool``/``v_pool`` ``[num_blocks, block_size, num_heads,
      head_dim]``: the layer's whole pools;
    - ``slots`` [S]: the absolute position this step WRITES (each
      sequence's context length before its new token);
    - ``lens`` [S]: the valid length the attention READS (slots + 1 —
      the just-written token attends itself, exactly like the dense
      cached path's causal row).

    ``models/gpt.py::CausalSelfAttention`` dispatches on the ``paged``
    marker: a view threaded through ``caches=`` routes the block's
    attention to ``ops.paged_attention`` instead of the dense
    preallocated buffer.  Views flow through jit/scan like any other
    pytree; ``updated()`` is the functional write-back.
    """

    paged = True

    def __init__(self, k_pool, v_pool, block_table, slots, lens):
        self.k_pool = k_pool
        self.v_pool = v_pool
        self.block_table = block_table
        self.slots = slots
        self.lens = lens

    def updated(self, k_pool, v_pool):
        return PagedCacheView(k_pool, v_pool, self.block_table,
                              self.slots, self.lens)

    def tree_flatten(self):
        return ((self.k_pool, self.v_pool, self.block_table,
                 self.slots, self.lens), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


class PagedKVCache:
    """The pool + its host-side block allocator.

    Device state: ``pools`` — one (k_pool, v_pool) pair per layer,
    updated functionally by the engine after each compiled step
    (``set_arrays``).  Host state: a free list and the per-sequence
    owned-block lists.  Allocation never partially succeeds: asking
    for more blocks than are free changes nothing and returns False.
    """

    def __init__(self, num_layers, num_heads, head_dim, *,
                 block_size, num_blocks, dtype=None, device_init=True):
        import jax.numpy as jnp
        if num_blocks < 2:
            raise ValueError('num_blocks must be >= 2 (block 0 is the '
                             'reserved trash block)')
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.dtype = dtype or jnp.float32
        if device_init:
            shape = (self.num_blocks, self.block_size, self.num_heads,
                     self.head_dim)
            self.pools = [(jnp.zeros(shape, self.dtype),
                           jnp.zeros(shape, self.dtype))
                          for _ in range(self.num_layers)]
        else:           # allocator-only (churn tests, audits)
            self.pools = None
        # LIFO free list: freshly freed blocks are the warmest
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._owned = {}            # seq_id -> [block ids, in order]
        self._high_water = 0        # max blocks ever simultaneously owned

    # -- allocator ----------------------------------------------------------
    @property
    def free_blocks(self):
        return len(self._free)

    def owned(self, seq_id):
        return list(self._owned.get(seq_id, ()))

    def owners(self):
        """The sequences that own at least one block."""
        return [sid for sid, blocks in self._owned.items() if blocks]

    def can_cover(self, seq_id, num_positions):
        need = blocks_for(num_positions, self.block_size) \
            - len(self._owned.get(seq_id, ()))
        return need <= len(self._free)

    def ensure(self, seq_id, num_positions):
        """Grow `seq_id`'s block list to cover `num_positions` cache
        slots.  All-or-nothing: False (and no change) when the free
        list cannot cover the growth."""
        have = self._owned.setdefault(seq_id, [])
        need = blocks_for(num_positions, self.block_size) - len(have)
        if need <= 0:
            return True
        if need > len(self._free):
            return False
        for _ in range(need):
            have.append(self._free.pop())
        used = (self.num_blocks - 1) - len(self._free)
        if used > self._high_water:
            self._high_water = used
        return True

    @property
    def high_water_blocks(self):
        """Most blocks ever simultaneously owned (lifetime)."""
        return self._high_water

    def frag_report(self):
        """Pool-shape truth for the memory observatory: how BROKEN UP
        the pool is, not just how full.

        - ``free_runs`` / ``largest_free_run``: maximal runs of
          consecutive block ids in the free list — a pool can hold
          plenty of free blocks yet no contiguous span (irrelevant to
          correctness here, the classic fragmentation signal on
          allocators that ever need spans);
        - ``frag_frac``: 1 - largest_run/free (0 = one solid span);
        - ``seq_spread_max`` / ``seq_spread_mean``: per-sequence block
          spread, (max-min+1)/owned — how scattered each sequence's
          blocks sit in the pool (gather locality);
        - ``high_water_blocks``: lifetime peak of owned blocks (the
          number capacity planning actually wants).
        """
        usable = self.num_blocks - 1
        free = sorted(self._free)
        runs = []
        if free:
            start = prev = free[0]
            for b in free[1:]:
                if b == prev + 1:
                    prev = b
                    continue
                runs.append(prev - start + 1)
                start = prev = b
            runs.append(prev - start + 1)
        largest = max(runs) if runs else 0
        spreads = []
        for blocks in self._owned.values():
            if blocks:
                spreads.append(
                    (max(blocks) - min(blocks) + 1) / len(blocks))
        return {
            'num_blocks': self.num_blocks,
            'usable_blocks': usable,
            'free_blocks': len(free),
            'owned_blocks': usable - len(free),
            'owned_seqs': sum(1 for b in self._owned.values() if b),
            'free_runs': len(runs),
            'largest_free_run': largest,
            'frag_frac': round(1.0 - largest / len(free), 4)
            if free else 0.0,
            'seq_spread_max': round(max(spreads), 4) if spreads else 0.0,
            'seq_spread_mean': round(sum(spreads) / len(spreads), 4)
            if spreads else 0.0,
            'high_water_blocks': self._high_water,
        }

    def free_seq(self, seq_id):
        """Release every block `seq_id` owns; returns how many."""
        blocks = self._owned.pop(seq_id, [])
        self._free.extend(reversed(blocks))
        return len(blocks)

    def table_row(self, seq_id, width):
        """`seq_id`'s block table padded (with the trash block) to a
        fixed `width` — one row of a compiled step's table input."""
        blocks = self._owned.get(seq_id, ())
        if len(blocks) > width:
            raise ValueError(
                f'sequence {seq_id} owns {len(blocks)} blocks > table '
                f'width {width}')
        row = np.full((width,), TRASH_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        return row

    def audit(self):
        """Invariant check; returns a list of violation strings (empty
        = healthy).  The churn property tests call this after every
        mutation."""
        problems = []
        seen = {}
        for sid, blocks in self._owned.items():
            for b in blocks:
                if b == TRASH_BLOCK or not 0 < b < self.num_blocks:
                    problems.append(f'seq {sid} owns illegal block {b}')
                if b in seen:
                    problems.append(
                        f'block {b} aliased by seqs {seen[b]} and {sid}')
                seen[b] = sid
        free = set(self._free)
        if len(free) != len(self._free):
            problems.append('free list holds duplicates')
        both = free & set(seen)
        if both:
            problems.append(f'blocks {sorted(both)} both free and owned')
        if TRASH_BLOCK in free:
            problems.append('trash block on the free list')
        if len(free) + len(seen) != self.num_blocks - 1:
            problems.append(
                f'leak: {self.num_blocks - 1 - len(free) - len(seen)} '
                'block(s) neither free nor owned')
        return problems

    # -- device pools -------------------------------------------------------
    # What the engine's compiled modules see of the cache: its arrays
    # as ONE pytree (donated in, returned whole), and how a prefill
    # fills them and a decode step views them.  `where` is the block
    # ids [B, blocks of the bucket] of a prefill and the tables
    # [S, table width] of a decode.
    def arrays(self):
        ks, vs = (tuple(x) for x in zip(*self.pools))
        return ks, vs

    def set_arrays(self, arrays):
        """Functional write-back after a compiled step."""
        self.pools = list(zip(*arrays))

    def prefill_where(self, seq_ids, rows, bucket):
        """Block ids [rows, blocks of the bucket] for a prefill chunk;
        rows past `seq_ids` are padding and point at the trash block."""
        nblk = blocks_for(bucket, self.block_size)
        where = np.zeros((rows, nblk), np.int32)
        for i, sid in enumerate(seq_ids):
            where[i] = self.owned(sid)[:nblk]
        return where

    def decode_where(self, plan):
        return plan.tables

    def idle_where(self, batch, width):
        """A decode's `where` that touches nothing kept (warm-up and
        example arguments): every row on the trash block."""
        return np.zeros((batch, width), np.int32)

    # what the decode modules' fingerprints call their attention path
    path_key = 'paged'
    # the order of a pool's axes, in every module's fingerprint: a
    # module exported against another order has the same avals at
    # block_size == num_heads and must not be loaded against this one
    layout_key = 'block,position,head,dim'

    def decode_path(self, model, batch, width):
        """'kernel' or 'gather': the same gate, on the same operands,
        that paged_attention asks when a decode module is traced."""
        from ..ops.paged_attention import can_use_pallas
        del model
        return 'kernel' if can_use_pallas(
            self.pools[0][0], jax.ShapeDtypeStruct(
                (batch, width), jnp.int32)) else 'gather'

    def prefill_caches(self, model, rows, bucket, lengths):
        """Dense per-layer buffers for the bucket, block-rounded."""
        del lengths
        nblk = blocks_for(bucket, self.block_size)
        return model.init_decode_caches(rows, nblk * self.block_size)

    def store_prefill(self, arrays, caches, where):
        """Every row's block-rounded KV scattered through its own
        block-table row."""
        from ..parallel.api import maybe_shard
        from ..ops.paged_attention import POOL_SPEC
        ks, vs = arrays
        B, nblk = where.shape
        nh, bs, hd = self.num_heads, self.block_size, self.head_dim
        new_ks, new_vs = [], []
        for (kbuf, vbuf), kp, vp in zip(caches, ks, vs):
            kbuf = kbuf.value if hasattr(kbuf, 'value') else kbuf
            vbuf = vbuf.value if hasattr(vbuf, 'value') else vbuf
            # [B, nh, Pc, hd] -> [B, nblk, bs, nh, hd] block rows
            kb = jnp.transpose(
                kbuf.reshape(B, nh, nblk, bs, hd), (0, 2, 3, 1, 4))
            vb = jnp.transpose(
                vbuf.reshape(B, nh, nblk, bs, hd), (0, 2, 3, 1, 4))
            kp = maybe_shard(kp, POOL_SPEC)
            vp = maybe_shard(vp, POOL_SPEC)
            new_ks.append(kp.at[where].set(kb.astype(kp.dtype)))
            new_vs.append(vp.at[where].set(vb.astype(vp.dtype)))
        return tuple(new_ks), tuple(new_vs)

    def constrain(self, arrays):
        from ..parallel.api import maybe_shard
        from ..ops.paged_attention import POOL_SPEC
        ks, vs = arrays
        return (tuple(maybe_shard(k, POOL_SPEC) for k in ks),
                tuple(maybe_shard(v, POOL_SPEC) for v in vs))

    def decode_views(self, arrays, where, ctx, active):
        del active
        ks, vs = arrays
        return [PagedCacheView(k, v, where, ctx, ctx + 1)
                for k, v in zip(ks, vs)]

    def arrays_of(self, views):
        return (tuple(v.k_pool for v in views),
                tuple(v.v_pool for v in views))

    def layer_view(self, layer, block_tables, slots, lens):
        k, v = self.pools[layer]
        return PagedCacheView(k, v, block_tables, slots, lens)


# -- one state of fixed size a sequence -----------------------------------------
@jax.tree_util.register_pytree_node_class
class RecurrentStateView:
    """One layer's recurrent state as a compiled module sees it.

    In a decode step: the whole arrays `S [slots, ...]` and `z`, the
    rows' `slots` (distinct) and which rows are `active`.  In a
    prefill: `S` and `z` are None on the way in (the empty state) and
    the rows' final states on the way out, `lengths` the rows' true
    lengths.  `updated()` is the functional write-back.
    """

    def __init__(self, S=None, z=None, slots=None, active=None,
                 lengths=None):
        self.S, self.z = S, z
        self.slots, self.active, self.lengths = slots, active, lengths

    def updated(self, S, z):
        return RecurrentStateView(S, z, self.slots, self.active,
                                  self.lengths)

    def tree_flatten(self):
        return ((self.S, self.z, self.slots, self.active,
                 self.lengths), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


class RecurrentStateCache:
    """Per layer `S [slots, kv heads, value_dim, features]` and
    `z [slots, kv heads, features]`, float32 (an accumulator over the
    whole sequence), one slot a live sequence whatever its length.

    It answers the calls the scheduler makes of the paged pool, with
    one "block" a sequence: `ensure` succeeds once a slot is held, so
    nothing is ever reserved a span and nothing is ever preempted.
    Ids run 1..slots as block ids do (0 is what a plan's padding rows
    carry, `TRASH_BLOCK`; no memory stands behind it): the device row
    of id `i` is `i - 1`.  A slot is overwritten whole by the prefill
    that takes it, so a freed slot is not zeroed.
    """

    # no parameter: nothing serves another dtype yet, and a benchmark
    # cell's probe holds the state to this one (its `state_rel_tol`)
    dtype = jnp.float32

    def __init__(self, num_layers, num_kv_heads, value_dim, features, *,
                 slots, max_model_len, device_init=True):
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.value_dim = int(value_dim)
        self.features = int(features)
        self.slots = int(slots)
        # one "block" covers a whole sequence
        self.block_size = int(max_model_len)
        self.num_blocks = self.slots + 1
        if device_init:
            s_shape = (self.slots, self.num_kv_heads, self.value_dim,
                       self.features)
            self.states = [(jnp.zeros(s_shape, self.dtype),
                            jnp.zeros(s_shape[:2] + s_shape[3:],
                                      self.dtype))
                           for _ in range(self.num_layers)]
        else:
            self.states = None
        self._free = list(range(self.slots, 0, -1))
        self._owner = {}             # seq_id -> slot id
        self._high_water = 0

    @property
    def bytes_per_slot(self):
        return self.num_layers * self.num_kv_heads * self.features \
            * (self.value_dim + 1) * jnp.dtype(self.dtype).itemsize

    @property
    def state_bytes(self):
        return self.slots * self.bytes_per_slot

    # -- allocator ----------------------------------------------------------
    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def high_water_blocks(self):
        return self._high_water

    def owned(self, seq_id):
        return [self._owner[seq_id]] if seq_id in self._owner else []

    def owners(self):
        return list(self._owner)

    def ensure(self, seq_id, num_positions):
        """True once `seq_id` holds a slot, whatever the length."""
        if seq_id in self._owner:
            return True
        if not self._free:
            return False
        self._owner[seq_id] = self._free.pop()
        self._high_water = max(self._high_water, len(self._owner))
        return True

    def free_seq(self, seq_id):
        slot = self._owner.pop(seq_id, None)
        if slot is None:
            return 0
        self._free.append(slot)
        return 1

    def table_row(self, seq_id, width):
        row = np.full((width,), TRASH_BLOCK, np.int32)
        row[:1] = self.owned(seq_id)
        return row

    def frag_report(self):
        return {'num_blocks': self.num_blocks, 'usable_blocks': self.slots,
                'free_blocks': len(self._free),
                'owned_blocks': len(self._owner),
                'owned_seqs': len(self._owner),
                'frag_frac': 0.0, 'largest_free_run': len(self._free),
                'high_water_blocks': self._high_water}

    def audit(self):
        """A slot with two owners, a slot both free and owned, an
        illegal id, a leak.  (An owner without a live request and a
        live request without a slot are the scheduler's to see: it
        knows the requests.)"""
        problems = []
        seen = {}
        for sid, slot in self._owner.items():
            if not 0 < slot <= self.slots:
                problems.append(f'seq {sid} owns illegal slot {slot}')
            if slot in seen:
                problems.append(
                    f'slot {slot} aliased by seqs {seen[slot]} and {sid}')
            seen[slot] = sid
        free = set(self._free)
        if len(free) != len(self._free):
            problems.append('free list holds duplicates')
        if free & set(seen):
            problems.append(
                f'slots {sorted(free & set(seen))} both free and owned')
        if len(free) + len(seen) != self.slots:
            problems.append(
                f'leak: {self.slots - len(free) - len(seen)} slot(s) '
                'neither free nor owned')
        return problems

    # -- device side ----------------------------------------------------------
    def arrays(self):
        Ss, zs = (tuple(x) for x in zip(*self.states))
        return Ss, zs

    def set_arrays(self, arrays):
        self.states = list(zip(*arrays))

    def prefill_where(self, seq_ids, rows, bucket):
        """Device rows [rows] a prefill chunk writes: the sequences'
        own; a padding row names the row past the last, which the
        write drops."""
        del bucket
        where = np.full((rows,), self.slots, np.int32)
        where[:len(seq_ids)] = [self._owner[sid] - 1 for sid in seq_ids]
        return where

    def decode_where(self, plan):
        """Device rows [batch] of a decode plan, DISTINCT (the decode
        update rewrites each row's slot in place): a padding row names
        a slot no live sequence holds, which its inactive update
        leaves as it was."""
        ids = [int(t) for t in plan.tables[:len(plan.requests), 0]]
        held = set(ids)
        spare = (s for s in range(1, self.slots + 1) if s not in held)
        ids += [next(spare) for _ in range(plan.batch - len(ids))]
        return np.asarray(ids, np.int32) - 1

    def idle_where(self, batch, width):
        del width
        return np.arange(batch, dtype=np.int32)

    path_key = 'state'
    layout_key = 'slot,head,value,feature'

    def decode_path(self, model, batch, width):
        """'kernel' or 'plain': the gate retention_decode asks."""
        from ..ops.power_retention import can_use_pallas
        del width
        cfg = model.config
        return 'kernel' if can_use_pallas(
            self.states[0][0], jax.ShapeDtypeStruct(
                (batch, cfg.num_heads, cfg.head_dim), jnp.float32)) \
            else 'plain'

    def prefill_caches(self, model, rows, bucket, lengths):
        del model, rows, bucket
        return [RecurrentStateView(lengths=lengths)
                for _ in range(self.num_layers)]

    def store_prefill(self, arrays, caches, where):
        """Each row's final (S, z) into its slot, whole."""
        Ss, zs = arrays
        with jax.named_scope('state.write'):
            return (tuple(S.at[where].set(c.S.astype(S.dtype), mode='drop')
                          for S, c in zip(Ss, caches)),
                    tuple(z.at[where].set(c.z.astype(z.dtype), mode='drop')
                          for z, c in zip(zs, caches)))

    def constrain(self, arrays):
        return arrays

    def decode_views(self, arrays, where, ctx, active):
        del ctx
        return [RecurrentStateView(S, z, where, active)
                for S, z in zip(*arrays)]

    def arrays_of(self, views):
        return tuple(v.S for v in views), tuple(v.z for v in views)
