"""HostOffloadEmbedding — the parameter-server substitute.

Reference analogue: the sparse-table tests around
fleet/runtime/the_one_ps.py (async push/pull of embedding rows);
here the server is the host process itself.
"""
import numpy as np
import pytest  # noqa: F401

import paddle_tpu as paddle
from jax import shard_map
from paddle_tpu import nn
from paddle_tpu.incubate import HostOffloadEmbedding


def _ids(*shape, hi=50, seed=0):
    return np.random.RandomState(seed).randint(0, hi, shape) \
        .astype('int64')


class TestHostOffloadEmbedding:
    def test_forward_matches_table(self):
        emb = HostOffloadEmbedding(50, 8, seed=0)
        ids = _ids(4, 3)
        out = np.asarray(emb(paddle.to_tensor(ids)).numpy())
        np.testing.assert_allclose(out, emb.table[ids], rtol=1e-6)

    def test_backward_updates_host_table_sgd(self):
        emb = HostOffloadEmbedding(50, 8, learning_rate=0.5, seed=0)
        ids = np.asarray([[1, 2]], 'int64')
        before = emb.table.copy()
        out = emb(paddle.to_tensor(ids))
        out.sum().backward()
        # d(sum)/d(row) = 1 -> row -= lr * 1
        np.testing.assert_allclose(emb.table[1], before[1] - 0.5,
                                   rtol=1e-5)
        np.testing.assert_allclose(emb.table[2], before[2] - 0.5,
                                   rtol=1e-5)
        np.testing.assert_allclose(emb.table[3], before[3], rtol=1e-7)

    def test_duplicate_ids_accumulate(self):
        emb = HostOffloadEmbedding(50, 4, learning_rate=1.0, seed=0)
        ids = np.asarray([[7, 7, 7]], 'int64')
        before = emb.table[7].copy()
        emb(paddle.to_tensor(ids)).sum().backward()
        np.testing.assert_allclose(emb.table[7], before - 3.0,
                                   rtol=1e-5)

    def test_adagrad_rule(self):
        emb = HostOffloadEmbedding(50, 4, learning_rate=1.0,
                                   optimizer='adagrad', seed=0)
        ids = np.asarray([[5]], 'int64')
        before = emb.table[5].copy()
        emb(paddle.to_tensor(ids)).sum().backward()
        # g=1: acc=1, step = 1/sqrt(1+eps) ~= 1
        np.testing.assert_allclose(emb.table[5], before - 1.0,
                                   rtol=1e-4)
        emb(paddle.to_tensor(ids)).sum().backward()
        # second hit: acc=2, step = 1/sqrt(2)
        np.testing.assert_allclose(
            emb.table[5], before - 1.0 - 1.0 / np.sqrt(2), rtol=1e-4)

    def test_frozen_table(self):
        emb = HostOffloadEmbedding(50, 4, trainable=False, seed=0)
        ids = np.asarray([[3]], 'int64')
        before = emb.table.copy()
        emb(paddle.to_tensor(ids)).sum().backward()
        np.testing.assert_allclose(emb.table, before, rtol=1e-7)

    def test_trains_inside_jitted_trainer(self):
        """The PS pattern end-to-end: dense params update on device,
        the sparse table updates host-side through the compiled step's
        callbacks — loss decreases."""
        from paddle_tpu.parallel import ParallelTrainer
        paddle.seed(0)

        class CTR(nn.Layer):
            def __init__(self):
                super().__init__()
                self.emb = HostOffloadEmbedding(1000, 8,
                                                learning_rate=0.2,
                                                seed=1)
                self.mlp = nn.Sequential(nn.Linear(3 * 8, 16),
                                         nn.ReLU(), nn.Linear(16, 1))

            def forward(self, ids):
                e = self.emb(ids)
                B = e.shape[0]
                from paddle_tpu.tensor import manipulation
                return self.mlp(manipulation.reshape(e, [B, -1]))

        model = CTR()
        opt = paddle.optimizer.Adam(1e-2,
                                    parameters=model.parameters())
        bce = nn.BCEWithLogitsLoss()
        tr = ParallelTrainer(model, opt, lambda o, y: bce(o, y))
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 1000, (64, 3)).astype('int64')
        y = (ids.sum(-1, keepdims=True) % 2).astype('float32')
        table0 = model.emb.table.copy()
        first = float(np.asarray(tr.step(ids, y)))
        for _ in range(30):
            last = float(np.asarray(tr.step(ids, y)))
        assert last < first, (first, last)
        assert np.abs(model.emb.table - table0).max() > 1e-4  # host push ran

    def test_state_dict_roundtrip(self):
        emb = HostOffloadEmbedding(20, 4, optimizer='adagrad', seed=0)
        emb(paddle.to_tensor(_ids(2, 2, hi=20))).sum().backward()
        state = emb.state_dict()
        assert '_extra_state' in state
        emb2 = HostOffloadEmbedding(20, 4, optimizer='adagrad', seed=9)
        emb2.set_state_dict(state)
        np.testing.assert_allclose(emb2.table, emb.table, rtol=1e-7)
        np.testing.assert_allclose(emb2._accum, emb._accum, rtol=1e-7)

    def test_parent_model_state_dict_carries_table(self):
        """The table must survive a WHOLE-MODEL save/restore (it rides
        parents' state_dicts via the extra-state hook), and the saved
        snapshot must not alias the live mutating table."""

        class M(nn.Layer):
            def __init__(self, seed):
                super().__init__()
                self.emb = HostOffloadEmbedding(30, 4, seed=seed,
                                                learning_rate=0.5)
                self.head = nn.Linear(4, 1)

            def forward(self, ids):
                return self.head(self.emb(ids))

        paddle.seed(0)
        m = M(seed=1)
        state = m.state_dict()
        assert 'emb._extra_state' in state
        snap = state['emb._extra_state']['table'].copy()
        # keep training: the snapshot must not follow the live table
        m(paddle.to_tensor(_ids(4, 2, hi=30))).sum().backward()
        np.testing.assert_allclose(state['emb._extra_state']['table'],
                                   snap, rtol=1e-7)
        m2 = M(seed=7)
        m2.set_state_dict(state)
        np.testing.assert_allclose(m2.emb.table, snap, rtol=1e-7)

    def test_oob_ids_raise(self):
        emb = HostOffloadEmbedding(10, 4, seed=0)
        with pytest.raises(Exception, match='out of range'):
            np.asarray(emb(paddle.to_tensor(
                np.asarray([[11]], 'int64'))).numpy())

    def test_extra_state_shape_mismatch_raises(self):
        emb = HostOffloadEmbedding(20, 4, seed=0)
        emb2 = HostOffloadEmbedding(20, 8, seed=0)
        with pytest.raises(ValueError, match='shape mismatch'):
            emb2.set_extra_state(emb.get_extra_state())


class TestEntryAdmission:
    """Entry admission configs (reference distributed/entry_attr.py)
    gating the host-side sparse update."""

    def _push_once(self, emb, ids):
        x = paddle.to_tensor(np.asarray(ids, 'int64'))
        out = emb(x)
        out.sum().backward()

    def test_count_filter_blocks_until_threshold(self):
        from paddle_tpu.distributed import CountFilterEntry
        paddle.seed(0)
        emb = HostOffloadEmbedding(10, 4, learning_rate=1.0,
                                   entry=CountFilterEntry(2))
        before = emb.table[3].copy()
        self._push_once(emb, [3])          # count=1 < 2: no learning
        np.testing.assert_allclose(emb.table[3], before)
        self._push_once(emb, [3])          # count=2: admitted
        assert not np.allclose(emb.table[3], before)

    def test_count_filter_counts_duplicates(self):
        from paddle_tpu.distributed import CountFilterEntry
        paddle.seed(0)
        emb = HostOffloadEmbedding(10, 4, learning_rate=1.0,
                                   entry=CountFilterEntry(2))
        before = emb.table[5].copy()
        self._push_once(emb, [5, 5])       # two shows in one batch
        assert not np.allclose(emb.table[5], before)

    def test_probability_entry_is_sticky(self):
        from paddle_tpu.distributed import ProbabilityEntry
        paddle.seed(0)
        emb = HostOffloadEmbedding(50, 4, learning_rate=1.0,
                                   entry=ProbabilityEntry(0.5), seed=0)
        before = emb.table.copy()
        self._push_once(emb, list(range(50)))
        changed = ~np.isclose(emb.table, before).all(axis=1)
        # ~half admitted; and the decision is per-row sticky
        assert 5 < changed.sum() < 45
        mid = emb.table.copy()
        self._push_once(emb, list(range(50)))
        changed2 = ~np.isclose(emb.table, mid).all(axis=1)
        np.testing.assert_array_equal(changed, changed2)

    def test_entry_validation(self):
        from paddle_tpu.distributed import (ProbabilityEntry,
                                            CountFilterEntry)
        with pytest.raises(ValueError):
            ProbabilityEntry(1.5)
        with pytest.raises(ValueError):
            CountFilterEntry(-1)
        with pytest.raises(TypeError):
            HostOffloadEmbedding(4, 2, entry=object())


class TestFleetDatasets:
    """InMemoryDataset/QueueDataset (reference fleet/dataset/dataset.py)."""

    def _write_files(self, tmp_path):
        f1 = tmp_path / 'a.txt'
        f2 = tmp_path / 'b.txt'
        f1.write_text('1 0.5 0.25\n2 1.5 1.25\n')
        f2.write_text('3 2.5 2.25\n')
        return [str(f1), str(f2)]

    def _specs(self):
        from paddle_tpu.static import InputSpec
        lab = InputSpec([None, 1], 'int64', 'label')
        den = InputSpec([None, 2], 'float32', 'dense')
        return [lab, den]

    def test_queue_dataset_streams(self, tmp_path):
        from paddle_tpu.distributed import QueueDataset
        ds = QueueDataset()
        ds.init(batch_size=2, use_var=self._specs())
        ds.set_filelist(self._write_files(tmp_path))
        rows = list(ds)
        assert len(rows) == 3
        lab, den = rows[0]
        np.testing.assert_array_equal(lab, [1])
        np.testing.assert_allclose(den, [0.5, 0.25])

    def test_inmemory_shuffle_and_sizes(self, tmp_path):
        from paddle_tpu.distributed import InMemoryDataset
        ds = InMemoryDataset()
        ds.init(batch_size=2, use_var=self._specs())
        ds.set_filelist(self._write_files(tmp_path))
        with pytest.raises(RuntimeError):
            iter(ds)
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 3
        ds.local_shuffle()
        labels = sorted(int(r[0][0]) for r in ds)
        assert labels == [1, 2, 3]
        ds.release_memory()
        assert ds.get_memory_data_size() == 0

    def test_feeds_dataloader(self, tmp_path):
        from paddle_tpu.distributed import InMemoryDataset
        from paddle_tpu.io import DataLoader
        ds = InMemoryDataset()
        ds.init(batch_size=2, use_var=self._specs())
        ds.set_filelist(self._write_files(tmp_path))
        ds.load_into_memory()
        dl = DataLoader(ds.as_dataset(), batch_size=2, drop_last=False)
        batches = list(dl)
        assert len(batches) == 2
        assert batches[0][0].shape[0] == 2


class TestDistributedSplit:
    """paddle.distributed.split (reference collective.py:1108) routed
    through the TP layers."""

    def test_linear_row_and_col(self):
        from paddle_tpu.distributed import split
        from paddle_tpu.distributed import env as dist_env
        dist_env.set_mesh(None)
        paddle.seed(0)
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype('float32'))
        y0 = split(x, (8, 6), 'linear', axis=0, num_partitions=2)
        assert y0.shape == [2, 6]
        y1 = split(x, (8, 6), 'linear', axis=1, num_partitions=2)
        assert y1.shape == [2, 6]

    def test_embedding(self):
        from paddle_tpu.distributed import split
        from paddle_tpu.distributed import env as dist_env
        dist_env.set_mesh(None)
        paddle.seed(0)
        ids = paddle.to_tensor(np.array([[1, 2]], 'int64'))
        out = split(ids, (16, 4), 'embedding', num_partitions=2)
        assert out.shape == [1, 2, 4]

    def test_bad_operation(self):
        from paddle_tpu.distributed import split
        with pytest.raises(ValueError):
            split(paddle.ones([2, 2]), (2, 2), 'conv')

    def test_named_calls_reuse_one_layer(self):
        """With name=, repeated eager calls must hit ONE weight (else a
        training loop re-randomizes each step — r2 advisor finding)."""
        from paddle_tpu.distributed import split
        from paddle_tpu.distributed import env as dist_env
        dist_env.set_mesh(None)
        paddle.seed(3)
        x = paddle.to_tensor(
            np.random.RandomState(1).randn(2, 8).astype('float32'))
        a = split(x, (8, 6), 'linear', axis=1, name='reuse_probe')
        b = split(x, (8, 6), 'linear', axis=1, name='reuse_probe')
        np.testing.assert_array_equal(np.asarray(a.value),
                                      np.asarray(b.value))

    def test_unnamed_eager_calls_are_fresh(self):
        """Without name=, each call builds fresh weights (reference
        dygraph semantics) — two loop iterations at ONE source line must
        NOT silently share a layer."""
        from paddle_tpu.distributed import split
        from paddle_tpu.distributed import env as dist_env
        dist_env.set_mesh(None)
        paddle.seed(4)
        x = paddle.to_tensor(
            np.random.RandomState(2).randn(2, 8).astype('float32'))
        outs = [split(x, (8, 8), 'linear', axis=1) for _ in range(2)]
        assert not np.allclose(np.asarray(outs[0].value),
                               np.asarray(outs[1].value))


class TestNativeSlotReader:
    """C++ MultiSlot parser (io/native/slotreader.cpp — reference
    data_feed.cc counterpart) vs the Python line parser."""

    def test_native_matches_python(self, tmp_path):
        from paddle_tpu.io.native import slotreader
        if not slotreader.available():
            pytest.skip('no compiler')
        f = tmp_path / 'part-0'
        f.write_text('1 0.5 0.25\n2 1.5 1.25\n3 -2.5 1e-3\n')
        cols = slotreader.parse_file(str(f), [1, 2], [True, False])
        np.testing.assert_array_equal(cols[0].ravel(), [1, 2, 3])
        assert cols[0].dtype == np.int64
        np.testing.assert_allclose(
            cols[1], [[0.5, 0.25], [1.5, 1.25], [-2.5, 1e-3]],
            rtol=1e-6)
        assert cols[1].dtype == np.float32

    def test_malformed_file_raises(self, tmp_path):
        from paddle_tpu.io.native import slotreader
        if not slotreader.available():
            pytest.skip('no compiler')
        f = tmp_path / 'bad'
        f.write_text('1 notanumber 3\n')
        with pytest.raises(ValueError, match='slotreader'):
            slotreader.parse_file(str(f), [1, 2], [True, False])

    def test_dataset_uses_native_and_matches(self, tmp_path,
                                             monkeypatch):
        from paddle_tpu.io.native import slotreader
        if not slotreader.available():
            pytest.skip('no compiler')
        from paddle_tpu.distributed import QueueDataset
        from paddle_tpu.static import InputSpec
        calls = []
        real = slotreader.parse_file

        def counting(*a, **k):
            calls.append(a)
            return real(*a, **k)
        monkeypatch.setattr(slotreader, 'parse_file', counting)
        f = tmp_path / 'p0'
        f.write_text('\n'.join(
            f'{i} {i + 0.5} {i + 0.25}' for i in range(50)) + '\n')
        from paddle_tpu.distributed import InMemoryDataset
        ds = InMemoryDataset()
        ds.init(batch_size=2, use_var=[
            InputSpec([None, 1], 'int64', 'label'),
            InputSpec([None, 2], 'float32', 'dense')])
        ds.set_filelist([str(f)])
        ds.load_into_memory()   # the bulk native path
        rows = list(ds)
        assert calls, 'native parser was not invoked'
        assert len(rows) == 50
        lab, den = rows[7]
        np.testing.assert_array_equal(lab, [7])
        np.testing.assert_allclose(den, [7.5, 7.25])

    def test_int32_slots_use_python_parser(self, tmp_path):
        # native columns are int64/float32 only; an int32 slot must
        # keep its declared dtype via the Python path (bulk included)
        from paddle_tpu.distributed import InMemoryDataset
        from paddle_tpu.static import InputSpec
        f = tmp_path / 'p1'
        f.write_text('7 0.5\n')
        ds = InMemoryDataset()
        ds.init(batch_size=1, use_var=[
            InputSpec([None, 1], 'int32', 'label'),
            InputSpec([None, 1], 'float32', 'dense')])
        ds.set_filelist([str(f)])
        ds.load_into_memory()
        lab, den = next(iter(ds))
        assert lab.dtype == np.int32

    def test_queue_dataset_streams_bounded_chunks(self, tmp_path,
                                                  monkeypatch):
        # QueueDataset streams through BOUNDED native chunks
        # (sr_parse_buf), never the whole-file parse_file path
        from paddle_tpu.io.native import slotreader
        from paddle_tpu.distributed import QueueDataset, dataset as dmod
        from paddle_tpu.static import InputSpec
        if not slotreader.available():
            pytest.skip('no compiler')
        file_calls, buf_calls = [], []
        real_pb = slotreader.parse_bytes
        monkeypatch.setattr(
            slotreader, 'parse_file',
            lambda *a, **k: file_calls.append(a) or None)
        monkeypatch.setattr(
            slotreader, 'parse_bytes',
            lambda *a, **k: buf_calls.append(a) or real_pb(*a, **k))
        monkeypatch.setattr(dmod.DatasetBase, '_CHUNK', 32)  # tiny
        f = tmp_path / 'p3'
        f.write_text('\n'.join(f'{i} {i + 0.5}' for i in range(40))
                     + '\n')
        ds = QueueDataset()
        ds.init(batch_size=1, use_var=[
            InputSpec([None, 1], 'int64', 'label'),
            InputSpec([None, 1], 'float32', 'dense')])
        ds.set_filelist([str(f)])
        rows = list(ds)
        assert len(rows) == 40
        np.testing.assert_array_equal(rows[17][0], [17])
        assert not file_calls          # whole-file path never used
        assert len(buf_calls) > 1      # genuinely chunked

    def test_native_rejects_float_in_int_slot(self, tmp_path):
        from paddle_tpu.io.native import slotreader
        if not slotreader.available():
            pytest.skip('no compiler')
        f = tmp_path / 'p2'
        f.write_text('3.7 1.0\n')
        with pytest.raises(ValueError, match='bad int'):
            slotreader.parse_file(str(f), [1, 1], [True, False])


class TestShardedHostEmbedding:
    """Process-sharded PS path on the single-process virtual mesh: the
    same all_gather+psum routing the two-process test
    (test_multiprocess.py) exercises across real processes (reference
    the_one_ps.py:417 table distribution)."""

    def _mesh(self, n=8):
        import jax
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:n]).reshape(n), ('dp',))

    def test_sharded_lookup_matches_table(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.incubate import HostOffloadEmbedding

        emb = HostOffloadEmbedding(64, 4, learning_rate=1.0, seed=7)
        ref = emb.table.copy()
        mesh = self._mesh()
        ids = np.arange(16).astype('int64')

        f = shard_map(lambda i, a: emb._lookup_mp(i, a), mesh=mesh,
                      in_specs=(P('dp'), P()), out_specs=P('dp'))
        rows = jax.jit(f)(jnp.asarray(ids), jnp.zeros((1,), jnp.float32))
        np.testing.assert_allclose(np.asarray(rows), ref[ids], rtol=1e-6)

    def test_sharded_push_updates_owner_once(self):
        """Each touched row moves by exactly -lr (sum loss, grad 1):
        the first-local-partition gate must prevent double counting."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.incubate import HostOffloadEmbedding

        emb = HostOffloadEmbedding(64, 4, learning_rate=1.0, seed=9)
        ref = emb.table.copy()
        mesh = self._mesh()
        ids = np.arange(16).astype('int64')

        def loss(anchor, idv):
            out = emb._lookup_mp(idv, anchor)
            return jax.lax.psum(out.sum(), 'dp')

        f = shard_map(loss, mesh=mesh, in_specs=(P(), P('dp')),
                      out_specs=P())
        jax.jit(jax.grad(f))(jnp.zeros((1,), jnp.float32),
                             jnp.asarray(ids))
        jax.effects_barrier()
        np.testing.assert_allclose(emb.table[ids], ref[ids] - 1.0,
                                   rtol=1e-6)
        # untouched rows unchanged
        np.testing.assert_allclose(emb.table[32:], ref[32:], rtol=1e-6)

    def test_duplicate_ids_accumulate(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.incubate import HostOffloadEmbedding

        emb = HostOffloadEmbedding(64, 4, learning_rate=1.0, seed=3)
        ref = emb.table.copy()
        mesh = self._mesh()
        ids = np.full((16,), 5, dtype='int64')   # one row, 16 refs

        def loss(anchor, idv):
            out = emb._lookup_mp(idv, anchor)
            return jax.lax.psum(out.sum(), 'dp')

        f = shard_map(loss, mesh=mesh, in_specs=(P(), P('dp')),
                      out_specs=P())
        jax.jit(jax.grad(f))(jnp.zeros((1,), jnp.float32),
                             jnp.asarray(ids))
        jax.effects_barrier()
        np.testing.assert_allclose(emb.table[5], ref[5] - 16.0,
                                   rtol=1e-5)

    def test_forward_routes_by_axis_binding(self):
        """Layer.forward picks the sharded path inside shard_map and the
        plain path outside — same layer object."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.incubate import HostOffloadEmbedding

        emb = HostOffloadEmbedding(32, 4, seed=5)
        ref = emb.table.copy()
        ids = np.arange(8).astype('int64')
        # eager (no axis): plain path
        out = emb(paddle.to_tensor(ids))
        np.testing.assert_allclose(np.asarray(out.value), ref[ids],
                                   rtol=1e-6)
        # inside shard_map: sharded path via the same forward()
        mesh = self._mesh()

        def fn(idv, anchor):
            return emb._lookup_mp(idv, anchor)
        f = shard_map(fn, mesh=mesh, in_specs=(P('dp'), P()),
                      out_specs=P('dp'))
        rows = jax.jit(f)(jnp.asarray(ids), jnp.zeros((1,), jnp.float32))
        np.testing.assert_allclose(np.asarray(rows), ref[ids], rtol=1e-6)

    def test_push_dedupes_across_replica_axes(self):
        """On a (dp, tp) mesh the push must land ONCE per owned row,
        not once per tp replica (r3 review finding), while lookups stay
        correct on every replica."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.incubate import HostOffloadEmbedding

        emb = HostOffloadEmbedding(32, 4, learning_rate=1.0, seed=13)
        ref = emb.table.copy()
        devs = np.array(jax.devices()[:8]).reshape(4, 2)
        mesh = Mesh(devs, ('dp', 'tp'))
        ids = np.arange(8).astype('int64')

        def loss(anchor, idv):
            out = emb._lookup_mp(idv, anchor)
            # replicate over tp like a TP layer's activations
            return jax.lax.psum(out.sum(), 'dp') / 1.0

        f = shard_map(loss, mesh=mesh,
                      in_specs=(P(), P('dp')), out_specs=P())
        jax.jit(jax.grad(f))(jnp.zeros((1,), jnp.float32),
                             jnp.asarray(ids))
        jax.effects_barrier()
        # grad of sum is 1 per row reference; exactly -1.0 moved (NOT
        # -2.0, which a per-tp-replica double push would produce)
        np.testing.assert_allclose(emb.table[ids], ref[ids] - 1.0,
                                   rtol=1e-6)


class TestNativeSparseUpdate:
    """C++ merge+rule pass (io/native/sparse_update.cpp) vs the numpy
    reference — the host-PS sparse optimizer (reference analogue: the
    C++ table optimizers behind the_one_ps.py)."""

    def test_sgd_matches_numpy(self):
        from paddle_tpu.io.native import sparse_update as native
        if not native.available():
            pytest.skip('no compiler')
        rs = np.random.RandomState(0)
        V, D, n = 50, 8, 200
        table_c = rs.randn(V, D).astype(np.float32)
        table_np = table_c.copy()
        ids = rs.randint(0, V, n).astype(np.int64)
        g = rs.randn(n, D).astype(np.float32)
        assert native.apply_update(table_c, None, ids, g, 0.1, 'sgd')
        uniq, inv = np.unique(ids, return_inverse=True)
        merged = np.zeros((uniq.shape[0], D), np.float32)
        np.add.at(merged, inv, g)
        table_np[uniq] -= 0.1 * merged
        np.testing.assert_allclose(table_c, table_np, rtol=1e-5,
                                   atol=1e-6)

    def test_adagrad_matches_numpy(self):
        from paddle_tpu.io.native import sparse_update as native
        if not native.available():
            pytest.skip('no compiler')
        rs = np.random.RandomState(1)
        V, D, n = 30, 4, 100
        table_c = rs.randn(V, D).astype(np.float32)
        accum_c = np.abs(rs.randn(V, D)).astype(np.float32)
        table_np, accum_np = table_c.copy(), accum_c.copy()
        ids = rs.randint(0, V, n).astype(np.int64)
        g = rs.randn(n, D).astype(np.float32)
        assert native.apply_update(table_c, accum_c, ids, g, 0.5,
                                   'adagrad')
        uniq, inv = np.unique(ids, return_inverse=True)
        merged = np.zeros((uniq.shape[0], D), np.float32)
        np.add.at(merged, inv, g)
        accum_np[uniq] += merged * merged
        table_np[uniq] -= 0.5 * merged / np.sqrt(accum_np[uniq] + 1e-10)
        np.testing.assert_allclose(accum_c, accum_np, rtol=1e-5)
        np.testing.assert_allclose(table_c, table_np, rtol=1e-5,
                                   atol=1e-6)

    def test_gather_matches_numpy(self):
        from paddle_tpu.io.native import sparse_update as native
        if not native.available():
            pytest.skip('no compiler')
        rs = np.random.RandomState(2)
        table = rs.randn(20, 6).astype(np.float32)
        ids = rs.randint(0, 20, 33).astype(np.int64)
        out = native.gather(table, ids)
        np.testing.assert_array_equal(out, table[ids])

    def test_embedding_uses_native_path(self, monkeypatch):
        """End-to-end through the layer: the push must actually ROUTE
        to the native pass (not silently fall back to numpy) and land
        the merged update."""
        from paddle_tpu.io.native import sparse_update as native
        if not native.available():
            pytest.skip('no compiler')
        calls = []
        real = native.apply_update

        def spy(*a, **k):
            out = real(*a, **k)
            calls.append(out)
            return out
        monkeypatch.setattr(native, 'apply_update', spy)
        paddle.seed(0)
        emb = HostOffloadEmbedding(40, 8, learning_rate=1.0, seed=4)
        before = emb.table.copy()
        ids = np.asarray([[3, 3, 7]], 'int64')
        emb(paddle.to_tensor(ids)).sum().backward()
        assert calls and all(calls), 'native sparse path did not run'
        np.testing.assert_allclose(emb.table[3], before[3] - 2.0,
                                   rtol=1e-5)
        np.testing.assert_allclose(emb.table[7], before[7] - 1.0,
                                   rtol=1e-5)


class TestFirstLocalOwnership:
    """The gather/push dedup flags are derived at runtime from each
    shard's ACTUAL owning process (io_callback + all_gather), not a
    contiguous-block assumption (advisor r3: interleaved process order
    silently doubled/dropped psum rows)."""

    def test_first_flags_interleaved(self):
        import jax.numpy as jnp
        from paddle_tpu.incubate.host_embedding import \
            first_flags_from_procs
        procs = jnp.asarray(np.array([0, 1, 0, 1], np.int32))
        flags = np.asarray(first_flags_from_procs(procs))
        # first device of proc0 is idx 0, of proc1 is idx 1 — NOT the
        # contiguous heuristic's {0, 2}
        assert flags.tolist() == [True, True, False, False]

    def test_first_flags_contiguous(self):
        import jax.numpy as jnp
        from paddle_tpu.incubate.host_embedding import \
            first_flags_from_procs
        procs = jnp.asarray(np.array([0, 0, 1, 1], np.int32))
        flags = np.asarray(first_flags_from_procs(procs))
        assert flags.tolist() == [True, False, True, False]

    def test_first_flags_single_process(self):
        import jax.numpy as jnp
        from paddle_tpu.incubate.host_embedding import \
            first_flags_from_procs
        procs = jnp.zeros(8, jnp.int32)
        flags = np.asarray(first_flags_from_procs(procs))
        assert flags.tolist() == [True] + [False] * 7

    def test_missing_process_raises_in_gather(self):
        # a psum group that sees fewer distinct processes than own a
        # table shard would silently drop the unseen hosts' rows
        emb = HostOffloadEmbedding(8, 2, seed=0)
        emb._nproc = 2
        with pytest.raises(RuntimeError, match='missing'):
            emb._mp_gather(np.int32(1), np.int32(1),
                           np.zeros((2, 3), np.int64))

    def test_sharded_lookup_on_virtual_mesh(self):
        # end-to-end through shard_map on the 8-device CPU mesh: the
        # runtime flags must reduce to "axis index 0 contributes" for
        # a single process, and the lookup must return exact rows
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()).reshape(8), ('dp',))
        emb = HostOffloadEmbedding(32, 4, seed=11)
        ids = np.arange(8, dtype='int64')

        def fwd(idv, anchor):
            return emb._lookup_mp(idv, anchor)

        f = shard_map(fwd, mesh=mesh, in_specs=(P('dp'), P()),
                          out_specs=P('dp'))
        rows = np.asarray(jax.jit(f)(jnp.asarray(ids),
                                     jnp.zeros((1,), jnp.float32)))
        np.testing.assert_allclose(rows, emb.table[ids], atol=1e-6)

    def test_dp_ranks_push_distinct_grads(self):
        # shard_axis='tp' under a (dp, tp) mesh: dp ranks hold
        # DIFFERENT batches, so BOTH their sparse updates must land
        # (gating the push on dp==0 would silently drop half the data)
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ('dp', 'tp'))
        emb = HostOffloadEmbedding(16, 4, learning_rate=1.0, seed=3,
                                   shard_axis='tp')
        before = emb.table.copy()
        # dp rank 0 looks up ids [1, 2]; dp rank 1 looks up [2, 3]
        ids = np.array([[1, 2], [2, 3]], dtype='int64')

        def loss(anchor, idv):
            out = emb._lookup_mp(idv, anchor)
            return jax.lax.psum(out.sum(), 'dp')

        g = shard_map(jax.grad(loss), mesh=mesh,
                          in_specs=(P(), P('dp')), out_specs=P())
        jax.jit(g)(jnp.zeros((1,), jnp.float32), jnp.asarray(ids))
        jax.effects_barrier()   # pushes are async io_callbacks
        # psum's transpose psums the replicated cotangent, so each
        # row's grad is dp_degree = 2.  id 1 and 3 are hit by one dp
        # rank, id 2 by BOTH (and each rank's tp-replicated copies
        # dedup to a single push)
        np.testing.assert_allclose(emb.table[1], before[1] - 2.0,
                                   atol=1e-5)
        np.testing.assert_allclose(emb.table[3], before[3] - 2.0,
                                   atol=1e-5)
        np.testing.assert_allclose(emb.table[2], before[2] - 4.0,
                                   atol=1e-5)

    def test_distinct_data_axes_rejected_as_replicated(self):
        with pytest.raises(ValueError, match='different data'):
            HostOffloadEmbedding(8, 2, replicated_axes=('dp', 'tp'))
        with pytest.raises(ValueError, match='different data'):
            HostOffloadEmbedding(8, 2, replicated_axes=('tp', 'sp'))
