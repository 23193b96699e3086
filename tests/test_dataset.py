"""tf.data-like pipeline semantics (paper §II-A)."""
import tempfile
import threading
import time

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # bare env (see `test` extra in pyproject.toml)
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.dataset import Dataset


class TestBasics:
    def test_from_tensor_slices_order(self):
        assert list(Dataset.from_tensor_slices([3, 1, 2])) == [3, 1, 2]

    def test_take_repeat(self):
        assert list(Dataset.range(3).repeat(2)) == [0, 1, 2, 0, 1, 2]
        assert list(Dataset.range(10).take(4)) == [0, 1, 2, 3]

    def test_batch_shapes(self):
        batches = list(Dataset.range(10).batch(3))
        assert [b.shape for b in batches] == [(3,), (3,), (3,)]  # drop remainder
        batches = list(Dataset.range(10).batch(3, drop_remainder=False))
        assert batches[-1].shape == (1,)

    def test_batch_pytree(self):
        ds = Dataset.from_tensor_slices(
            [(np.ones(2) * i, np.int32(i)) for i in range(4)]
        ).batch(2)
        imgs, labels = next(iter(ds))
        assert imgs.shape == (2, 2) and labels.shape == (2,)


class TestShuffle:
    @given(st.integers(0, 2**31 - 1), st.integers(1, 64))
    @settings(max_examples=20, deadline=None)
    def test_shuffle_is_permutation(self, seed, buf):
        items = list(range(100))
        out = list(Dataset.from_tensor_slices(items).shuffle(buf, seed=seed))
        assert sorted(out) == items

    def test_shuffle_deterministic_by_seed(self):
        a = list(Dataset.range(50).shuffle(16, seed=7))
        b = list(Dataset.range(50).shuffle(16, seed=7))
        c = list(Dataset.range(50).shuffle(16, seed=8))
        assert a == b
        assert a != c  # astronomically unlikely to collide

    def test_shuffle_actually_shuffles(self):
        out = list(Dataset.range(100).shuffle(100, seed=0))
        assert out != list(range(100))


class TestMap:
    def test_map_serial(self):
        assert list(Dataset.range(4).map(lambda x: x * 2)) == [0, 2, 4, 6]

    @pytest.mark.parametrize("threads", [2, 4])
    def test_map_parallel_deterministic_order(self, threads):
        out = list(Dataset.range(20).map(
            lambda x: x * 10, num_parallel_calls=threads))
        assert out == [x * 10 for x in range(20)]

    def test_map_parallel_completion_order_is_complete(self):
        def slow_even(x):
            time.sleep(0.02 if x % 2 == 0 else 0.0)
            return x

        out = list(Dataset.range(16).map(
            slow_even, num_parallel_calls=4, deterministic=False))
        assert sorted(out) == list(range(16))

    def test_map_parallel_uses_threads(self):
        """8 sleeps of 50ms on 8 threads must take far less than 400ms."""
        def slow(x):
            time.sleep(0.05)
            return x

        t0 = time.monotonic()
        out = list(Dataset.range(8).map(slow, num_parallel_calls=8))
        elapsed = time.monotonic() - t0
        assert sorted(out) == list(range(8))
        assert elapsed < 0.25, f"no thread overlap: {elapsed:.3f}s"


class TestErrorHandling:
    def test_ignore_errors_drops_bad(self):
        def maybe_fail(x):
            if x % 3 == 0:
                raise ValueError("boom")
            return x

        out = list(Dataset.range(10).map(maybe_fail).ignore_errors())
        assert out == [x for x in range(10) if x % 3 != 0]

    def test_error_propagates_without_ignore(self):
        def fail(x):
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError):
            list(Dataset.range(3).map(fail))


class TestCachePrefetch:
    def test_cache_second_epoch_no_recompute(self):
        calls = []

        def f(x):
            calls.append(x)
            return x

        ds = Dataset.range(5).map(f).cache()
        assert list(ds) == list(range(5))
        assert list(ds) == list(range(5))
        assert len(calls) == 5  # second epoch served from memory

    def test_prefetch_preserves_stream(self):
        out = list(Dataset.range(100).prefetch(4))
        assert out == list(range(100))

    def test_prefetch_error_propagates(self):
        def fail(x):
            if x == 5:
                raise RuntimeError("boom")
            return x

        with pytest.raises(RuntimeError):
            list(Dataset.range(10).map(fail).prefetch(2))


# ---------------------------------------------------------------------------
# O(1) iterator resume (PR 10): interleave_order replica, seekable shard
# streaming, ResumableIterator seek
# ---------------------------------------------------------------------------
from repro.core import records
from repro.core.dataset import (ResumableIterator, interleave_order,
                                interleave_state, sharded_image_pipeline,
                                sharded_record_dataset)
from repro.core.faults import FaultyStorage
from repro.core.storage import NativeStorage


class TestInterleaveOrder:
    def _real_order(self, counts, cyc, blk):
        """Ground truth: run the actual interleave over (src, idx) pairs."""
        ds = Dataset.from_tensor_slices(list(range(len(counts)))).interleave(
            lambda s: iter([(s, i) for i in range(counts[s])]),
            cycle_length=cyc, block_length=blk)
        return list(ds)

    @pytest.mark.parametrize("counts,cyc,blk", [
        ([4, 4, 4], 2, 2),        # exact block multiples (empty-turn case)
        ([5, 3, 7, 2, 6], 3, 2),  # uneven tails
        ([8], 4, 3),              # single source, cycle > sources
        ([2, 2], 4, 1),
        ([0, 3, 4], 2, 2),        # empty source retires on first turn
        ([3, 0, 0, 5, 1], 2, 3),
        ([6, 6], 1, 4),           # degenerate cycle: pure concatenation
    ])
    def test_matches_real_interleave(self, counts, cyc, blk):
        assert interleave_order(counts, cyc, blk) == \
            self._real_order(counts, cyc, blk)

    @given(st.lists(st.integers(0, 9), min_size=1, max_size=6),
           st.integers(1, 5), st.integers(1, 4))
    @settings(max_examples=30, deadline=None)
    def test_matches_real_interleave_property(self, counts, cyc, blk):
        assert interleave_order(counts, cyc, blk) == \
            self._real_order(counts, cyc, blk)

    def test_validates_args(self):
        with pytest.raises(ValueError):
            interleave_order([1], cycle_length=0)
        with pytest.raises(ValueError):
            interleave_order([1], block_length=0)

    def _resumed(self, counts, cyc, blk, offset, npc=0):
        """The real interleave started from ``interleave_state`` at
        ``offset``: (its stream, the sources it opened)."""
        opened = []

        def sub(s):
            opened.append(s)
            return iter([(s, i) for i in range(counts[s])])

        ds = Dataset.from_tensor_slices(list(range(len(counts)))).interleave(
            sub, cycle_length=cyc, block_length=blk, num_parallel_calls=npc,
            start=interleave_state(counts, cyc, blk, offset))
        return list(ds), opened

    def _check_every_offset(self, counts, cyc, blk, npc=0):
        order = interleave_order(counts, cyc, blk)
        for offset in range(len(order) + 2):
            tail, opened = self._resumed(counts, cyc, blk, offset, npc)
            assert tail == order[offset:], f"offset={offset}"
            # a source whose elements all lie before the cut is never
            # opened; an empty one is, as in the full stream, if it enters
            # the cycle after the cut
            entered = interleave_state(counts, cyc, blk, offset).entered
            wanted = {s for s, _ in tail} | {
                s for s in range(entered, len(counts)) if counts[s] == 0}
            assert sorted(opened) == sorted(wanted), f"offset={offset}"

    @pytest.mark.parametrize("npc", [0, 3])
    @pytest.mark.parametrize("counts,cyc,blk", [
        ([4, 4, 4], 2, 2),        # exact block multiples (empty-turn case)
        ([5, 3, 7, 2, 6], 3, 2),  # uneven tails
        ([8], 4, 3),              # single source, cycle > sources
        ([0, 3, 4], 2, 2),        # empty source retires on first turn
        ([3, 0, 0, 5, 1], 2, 3),
        ([6, 6], 1, 4),           # degenerate cycle: pure concatenation
    ])
    def test_state_resumes_real_interleave(self, counts, cyc, blk, npc):
        self._check_every_offset(counts, cyc, blk, npc)

    @given(st.lists(st.integers(0, 9), min_size=1, max_size=6),
           st.integers(1, 5), st.integers(1, 4))
    @settings(max_examples=30, deadline=None)
    def test_state_resumes_real_interleave_property(self, counts, cyc, blk):
        self._check_every_offset(counts, cyc, blk)

    def test_state_past_end_is_empty(self):
        state = interleave_state([3, 2], 2, 2, 5)
        assert state.cycle == () and state.entered == 2
        assert self._resumed([3, 2], 2, 2, 99) == ([], [])

    def test_start_refuses_quarantine(self):
        from repro.core.dataset import ShardQuarantine

        with pytest.raises(ValueError):
            Dataset.range(2).interleave(
                lambda s: iter([s]), quarantine=ShardQuarantine(),
                start=interleave_state([1, 1], 4, 1, 1))


class TestShardedRecordDataset:
    REC = 8

    def _mk_shards(self, storage, byte_sizes):
        paths = []
        for j, n in enumerate(byte_sizes):
            p = f"data/shard{j}.rec"
            storage.write_file(p, bytes((j * 16 + k) % 251 for k in range(n)))
            paths.append(p)
        return paths

    def test_seek_tail_matches_full_stream(self, tmp_storage):
        # short final records at 20 (4 bytes) and 33 (1 byte)
        paths = self._mk_shards(tmp_storage, [24, 20, 8, 33, 16])
        full = list(sharded_record_dataset(
            tmp_storage, paths, self.REC, cycle_length=2, block_length=2,
            seed=3))
        n = len(full)
        for start in (1, 3, n // 2, n - 1, n, n + 7):
            tail = list(sharded_record_dataset(
                tmp_storage, paths, self.REC, cycle_length=2, block_length=2,
                seed=3, start=start))
            assert tail == full[start:], f"start={start}"

    def test_seek_reads_no_skipped_records(self, tmp_storage):
        """Positioning is arithmetic: only the tail's records are read."""
        paths = self._mk_shards(tmp_storage, [24, 20, 8, 33, 16])
        full = list(sharded_record_dataset(tmp_storage, paths, self.REC,
                                           seed=1))
        counting = FaultyStorage(tmp_storage)  # unarmed: just an op log
        start = len(full) - 3
        tail = list(sharded_record_dataset(counting, paths, self.REC,
                                           seed=1, start=start))
        assert tail == full[start:]
        reads = [e for e in counting.op_log if e[0].startswith("read")]
        assert len(reads) == len(full) - start  # zero reads for the skip

    def test_seed_changes_order(self, tmp_storage):
        paths = self._mk_shards(tmp_storage, [32, 32, 32, 32, 32, 32])
        a = list(sharded_record_dataset(tmp_storage, paths, self.REC, seed=0))
        b = list(sharded_record_dataset(tmp_storage, paths, self.REC, seed=5))
        assert sorted(a) == sorted(b) and a != b


# shard record counts: ragged, one short shard (index 2, which worker 0 of
# two keeps) and counts that cycle_length/block_length do and do not divide
SEEK_COUNTS = [6, 7, 2, 6, 5, 6, 6, 6]
SEEK_BATCH = 4


@pytest.fixture(scope="module")
def ragged_corpus():
    with tempfile.TemporaryDirectory() as d:
        storage = NativeStorage(d)
        paths, labels = [], []
        for j, n in enumerate(SEEK_COUNTS):
            p, ls = records.write_sharded_image_dataset(
                storage, n, n, mean_hw=(12, 12), hw_jitter=0.0,
                n_classes=7, seed=j, prefix=f"s{j}")
            paths += p
            labels += ls
        yield storage, paths, labels


def _seed_placing(short: int, early: bool, num_shards: int) -> int:
    """A shuffle seed that puts shard ``short`` first or last among the
    shards worker 0 streams."""
    kept = list(range(len(SEEK_COUNTS)))[::num_shards]
    for seed in range(1000):
        order = list(Dataset.from_tensor_slices(kept).shuffle(len(SEEK_COUNTS),
                                                              seed=seed))
        if order[0 if early else -1] == short:
            return seed
    raise AssertionError("no seed places the short shard")


def _assert_batches_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestShardedImagePipelineSeek:
    """``sharded_image_pipeline(...).seek(k)`` is the full stream from batch
    ``k`` on, positioned arithmetically."""

    def _pipeline(self, storage, paths, labels, seed, cyc, blk, npc,
                  num_shards, batched, **kw):
        return sharded_image_pipeline(
            storage, paths, labels, batch_size=SEEK_BATCH, cycle_length=cyc,
            block_length=blk, num_parallel_calls=npc, out_hw=(8, 8),
            seed=seed, num_shards=num_shards, shard_index=0,
            batched_preprocess=batched, **kw)

    @pytest.mark.parametrize("batched", [None, "numpy"])
    @pytest.mark.parametrize("num_shards", [1, 2])
    @pytest.mark.parametrize("npc", [0, 4])
    @pytest.mark.parametrize("cyc,blk", [(2, 3), (3, 4), (4, 2)])
    @pytest.mark.parametrize("early", [True, False])
    def test_seek_equals_full_stream_tail(self, ragged_corpus, early, cyc,
                                          blk, npc, num_shards, batched):
        storage, paths, labels = ragged_corpus
        seed = _seed_placing(2, early, num_shards)
        ds = self._pipeline(storage, paths, labels, seed, cyc, blk, npc,
                            num_shards, batched)
        assert ds.seekable
        full = list(ds)
        assert len(full) == sum(SEEK_COUNTS[::num_shards]) // SEEK_BATCH
        for k in range(len(full) + 2):
            _assert_batches_equal(list(ds.seek(k)), full[k:])

    @pytest.mark.parametrize("cyc,blk", [(2, 3), (3, 4)])
    def test_seek_reads_no_shard_before_the_cut(self, ragged_corpus, cyc,
                                                blk):
        storage, paths, labels = ragged_corpus
        seed = _seed_placing(2, True, 1)
        shuffled = list(Dataset.from_tensor_slices(
            list(range(len(paths)))).shuffle(len(paths), seed=seed))
        order = interleave_order([SEEK_COUNTS[i] for i in shuffled], cyc, blk)
        full = list(self._pipeline(storage, paths, labels, seed, cyc, blk, 4,
                                   1, None))
        for k in range(1, len(full) + 1):
            counting = FaultyStorage(storage)  # unarmed: just an op log
            tail = list(self._pipeline(counting, paths, labels, seed, cyc,
                                       blk, 4, 1, None).seek(k))
            _assert_batches_equal(tail, full[k:])
            read = [p for op, p, _ in counting.op_log
                    if op.startswith("read")]
            wanted = {paths[shuffled[s]]
                      for s, _ in order[k * SEEK_BATCH:]}
            assert sorted(read) == sorted(wanted), f"k={k}"

    def test_no_seek_without_counts_or_with_quarantine(self, ragged_corpus):
        from repro.core.dataset import ShardQuarantine

        storage, paths, labels = ragged_corpus
        assert not sharded_image_pipeline(storage, paths).seekable
        assert sharded_image_pipeline(storage, paths).seek(1) is None
        quarantined = sharded_image_pipeline(
            storage, paths, labels, quarantine=ShardQuarantine())
        assert not quarantined.seekable
        assert quarantined.seek(1) is None
        assert not sharded_image_pipeline(storage, paths, labels,
                                          repeat=True).seekable
        # transformations do not carry the seek forward
        ds = sharded_image_pipeline(storage, paths, labels)
        assert ds.seekable and not ds.take(1).seekable


class TestResumableIteratorSeek:
    DATA = [[f"e{e}r{i}" for i in range(10)] for e in range(3)]

    def _seekable(self):
        data = self.DATA
        return lambda ep, start=0: Dataset.from_tensor_slices(
            data[ep % len(data)][start:])

    def _replay_only(self):
        data = self.DATA
        return lambda ep: Dataset.from_tensor_slices(data[ep % len(data)])

    def test_seekability_detected_from_signature(self):
        assert ResumableIterator(self._seekable()).state().get("seek") is True
        assert "seek" not in ResumableIterator(self._replay_only()).state()
        assert "seek" not in ResumableIterator(
            Dataset.range(4)).state()  # plain Dataset: never seekable

    def test_seek_restore_equals_replay_restore(self):
        it = ResumableIterator(self._seekable(), epochs=2)
        head = [next(it) for _ in range(7)]
        st = it.state()
        rest = list(it)  # uninterrupted continuation = ground truth

        seeked = ResumableIterator(self._seekable(), epochs=2)
        seeked.restore_state(st)
        assert list(seeked) == rest

        replayed = ResumableIterator(self._replay_only(), epochs=2)
        replayed.restore_state(st)  # same dict, "seek" key ignored
        assert list(replayed) == rest
        assert head == self.DATA[0][:7]

    def test_seek_restore_counts_metric(self):
        from repro import metrics

        it = ResumableIterator(self._seekable())
        reg = metrics.start()
        try:
            it.restore_state({"epoch": 0, "offset": 4, "version": 1})
            counters = reg.collect()["counters"]
            assert sum(v for k, v in counters.items()
                       if k.startswith("pipeline.resume_seeks")) == 1
            assert not any(k.startswith("pipeline.resume_skipped")
                           for k in counters)
        finally:
            metrics.stop()
        assert next(it) == self.DATA[0][4]

    def test_seek_past_epoch_end_rolls_epoch(self):
        it = ResumableIterator(self._seekable(), epochs=2)
        it.restore_state({"epoch": 0, "offset": len(self.DATA[0]),
                          "version": 1})
        assert next(it) == self.DATA[1][0]

    def _image_factory(self, corpus, replay=False):
        """Shaped like a training job's: ``ep -> pipeline``, no start.
        ``replay``: a transformation drops the pipeline's seek."""
        storage, paths, labels = corpus

        def epoch(ep):
            ds = sharded_image_pipeline(
                storage, paths, labels, batch_size=SEEK_BATCH,
                cycle_length=3, block_length=4, num_parallel_calls=2,
                out_hw=(8, 8), seed=ep)
            return ds.take(10 ** 6) if replay else ds
        return epoch

    def test_image_factory_without_start_restores_by_seek(self,
                                                          ragged_corpus):
        from repro import metrics

        state = {"epoch": 1, "offset": 5, "version": 1}
        replayed = ResumableIterator(self._image_factory(ragged_corpus,
                                                         replay=True))
        replayed.restore_state(state)
        it = ResumableIterator(self._image_factory(ragged_corpus))
        assert "seek" not in it.state()     # the factory takes no start
        reg = metrics.start()
        try:
            it.restore_state(state)
            counters = reg.collect()["counters"]
        finally:
            metrics.stop()
        assert sum(v for k, v in counters.items()
                   if k.startswith("pipeline.resume_seeks")) == 1
        assert not any(k.startswith("pipeline.resume_skipped")
                       for k in counters)
        assert it.state() == state
        _assert_batches_equal([next(it) for _ in range(3)],
                              [next(replayed) for _ in range(3)])
        it.close()
        replayed.close()

    def test_image_seek_traces_one_resume_seek_span(self, ragged_corpus):
        from repro import trace

        it = ResumableIterator(self._image_factory(ragged_corpus))
        tracer = trace.start()
        try:
            it.restore_state({"epoch": 0, "offset": 4, "version": 1})
        finally:
            trace.stop()
        it.close()
        names = [s.name for s in tracer.spans()
                 if s.name.startswith("resume_")]
        assert names == ["resume_seek:4@epoch0"]

    def test_image_seek_past_epoch_end_rolls_epoch(self, ragged_corpus):
        fresh = ResumableIterator(self._image_factory(ragged_corpus))
        n = len(list(self._image_factory(ragged_corpus)(0)))
        it = ResumableIterator(self._image_factory(ragged_corpus))
        it.restore_state({"epoch": 0, "offset": n, "version": 1})
        fresh.restore_state({"epoch": 1, "offset": 0, "version": 1})
        _assert_batches_equal([next(it)], [next(fresh)])
        assert it.state()["epoch"] == 1
        it.close()
        fresh.close()

    def test_e2e_sharded_factory_seek_without_replay_io(self, tmp_storage):
        rec = 8
        paths = []
        for j in range(4):
            p = f"data/s{j}.rec"
            tmp_storage.write_file(p, bytes(range(j * 32, j * 32 + 32)))
            paths.append(p)

        def factory_on(storage):
            return lambda ep, start=0: sharded_record_dataset(
                storage, paths, rec, cycle_length=2, block_length=2,
                seed=ep, start=start)

        it = ResumableIterator(factory_on(tmp_storage), epochs=1)
        head = [next(it) for _ in range(9)]
        st = it.state()
        rest = list(it)

        counting = FaultyStorage(tmp_storage)
        it2 = ResumableIterator(factory_on(counting), epochs=1)
        it2.restore_state(st)
        assert list(it2) == rest
        reads = [e for e in counting.op_log if e[0].startswith("read")]
        assert len(reads) == len(rest)  # none of the 9 head records re-read
        assert len(head) == 9
