"""End-to-end crash recovery: the paper's "restart quickly from a
checkpoint", actually proven.

The harness trains a tiny deterministic model whose final params fold in
*every consumed batch in order* (``w = w/2 + batch``), so bit-identical
final params after a kill+resume proves the resumed run consumed exactly
the golden sample stream — no skipped and no replayed samples relative to
the checkpointed pipeline position.  The kill sweep dies at **every write
op** of a full training run (data shards, index, meta, commit marker, GC
marker — i.e. mid-save and mid-GC), under the clean, torn-write and
reordered-fsync+crash fault models, plus mid-step abandonment and
mid-drain kills through the burst-buffer engine; transient faults are
absorbed in place by the retry layer.
"""
import tempfile

import numpy as np
import pytest

from repro.core.checkpoint import CheckpointSaver
from repro.core.dataset import Dataset, ResumableIterator
from repro.core.faults import FaultInjected, FaultyStorage, TransientFault
from repro.core.recovery import (CheckpointManager, latest_valid_step,
                                 list_steps, valid_steps, validate_step)
from repro.core.retry import RetryPolicy, RetryingStorage
from repro.core.storage import NativeStorage

FAST_RETRY = RetryPolicy(max_attempts=6, base_delay_s=1e-5, max_delay_s=1e-4)

N_PER_EPOCH = 6
N_STEPS = 8
CKPT_EVERY = 2
PREFIX = "ckpt/m"


def sample_value(epoch: int, i: int) -> np.float64:
    return np.float64(epoch * 1000 + i + 1)


def make_iter() -> ResumableIterator:
    return ResumableIterator(lambda ep: Dataset.from_tensor_slices(
        [sample_value(ep, i) for i in range(N_PER_EPOCH)]))


def make_setup(consumed):
    """State + step fn: ``w`` folds in every batch (order-sensitive)."""
    state = {"w": np.float64(0.0), "step": np.int64(0)}

    def train_step(state, batch):
        b = np.float64(batch)
        consumed.append(float(b))
        new = {"w": state["w"] * np.float64(0.5) + b,
               "step": state["step"] + np.int64(1)}
        return new, {"loss": b}

    return state, train_step


def make_trainer(checkpointer, consumed, it=None):
    from repro.train.trainer import Trainer

    state, step_fn = make_setup(consumed)
    it = it if it is not None else make_iter()
    return Trainer(step_fn, state, it, checkpointer=checkpointer,
                   ckpt_every=CKPT_EVERY)


def golden_run():
    """Fault-free reference: (final_w, consumed sample stream)."""
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(NativeStorage(d), PREFIX, keep_last=2)
        consumed = []
        tr = make_trainer(mgr, consumed)
        tr.run(N_STEPS)
        return float(np.asarray(tr.state["w"])), consumed


def finish_from_checkpoint(storage, golden_w, golden_stream, ctx="",
                           keep_last=2):
    """Restart on ``storage``, run to N_STEPS total, assert bit-identical
    params and an exactly-aligned sample stream."""
    mgr = CheckpointManager(storage, PREFIX, keep_last=keep_last)
    consumed = []
    tr = make_trainer(mgr, consumed)
    start = tr.recovered_step or 0
    tr.run(N_STEPS - start)
    assert float(np.asarray(tr.state["w"])) == golden_w, ctx
    assert consumed == golden_stream[start:], ctx
    return start


def count_write_ops():
    """Clean run: total write ops issued (the sweep's injection points)."""
    with tempfile.TemporaryDirectory() as d:
        faulty = FaultyStorage(NativeStorage(d))
        mgr = CheckpointManager(faulty, PREFIX, keep_last=2)
        tr = make_trainer(mgr, [])
        tr.run(N_STEPS)
        return sum(1 for op, _, _ in faulty.op_log
                   if op.startswith("write") or op == "append_file")


# ---------------------------------------------------------------------------
# the kill sweep: die at every write op, under every fault model
# ---------------------------------------------------------------------------
class TestKillSweep:
    @pytest.mark.parametrize("model", ["clean", "torn"])
    def test_kill_at_every_write_op_then_resume(self, model):
        """Mid-save and mid-GC kills: every write op of the run is an
        injection point (shards, index, meta, save marker, GC marker)."""
        golden_w, golden_stream = golden_run()
        n_ops = count_write_ops()
        assert n_ops >= 8, "sweep must cover shards+index+meta+markers"
        for k in range(n_ops):
            with tempfile.TemporaryDirectory() as d:
                faulty = FaultyStorage(NativeStorage(d))
                mgr = CheckpointManager(faulty, PREFIX, keep_last=2)
                tr = make_trainer(mgr, [])
                if model == "clean":
                    faulty.fail_after(k)
                else:
                    faulty.torn_write(0.5, n_ops=k)
                with pytest.raises(FaultInjected):
                    tr.run(N_STEPS)
                tr.close()
                faulty.heal()
                finish_from_checkpoint(faulty, golden_w, golden_stream,
                                       ctx=f"model={model}, op {k}/{n_ops}")

    def test_mid_step_abandonment_at_every_step(self):
        """Kill between steps (no storage fault): resume replays only the
        post-checkpoint tail and still lands on the golden bits."""
        golden_w, golden_stream = golden_run()
        for j in range(1, N_STEPS):
            with tempfile.TemporaryDirectory() as d:
                storage = NativeStorage(d)
                mgr = CheckpointManager(storage, PREFIX, keep_last=2)
                tr = make_trainer(mgr, [])
                tr.run(j)      # process dies here: no final checkpoint
                tr.close()
                start = finish_from_checkpoint(
                    storage, golden_w, golden_stream, ctx=f"killed at {j}")
                assert start <= j  # resumed at/before the kill point

    def test_reordered_fsync_crash_then_resume(self):
        """Power loss with volatile caches (sync=False saves): unsynced
        writes roll back / survive out of order; restart must walk back to
        whatever is structurally valid and still finish bit-identical."""
        golden_w, golden_stream = golden_run()
        for j in range(1, N_STEPS):
            for keep in ("last", "none"):
                with tempfile.TemporaryDirectory() as d:
                    faulty = FaultyStorage(
                        NativeStorage(d)).reordered_fsync()
                    mgr = CheckpointManager(faulty, PREFIX, keep_last=2,
                                            sync=False)
                    tr = make_trainer(mgr, [])
                    tr.run(j)
                    tr.close()
                    faulty.crash(keep=keep)
                    faulty.heal()
                    finish_from_checkpoint(
                        faulty, golden_w, golden_stream,
                        ctx=f"crash(keep={keep}) after {j}")

    def test_transient_faults_absorbed_in_place(self):
        """A flaky (not dead) device under a retry-wrapped manager: the run
        completes without any restart and matches golden exactly."""
        golden_w, golden_stream = golden_run()
        with tempfile.TemporaryDirectory() as d:
            faulty = FaultyStorage(NativeStorage(d)).transient(
                rate=0.2, ops=("read", "write"), seed=11)
            mgr = CheckpointManager(faulty, PREFIX, keep_last=2,
                                    retry_policy=FAST_RETRY)
            consumed = []
            tr = make_trainer(mgr, consumed)
            tr.run(N_STEPS)
            assert float(np.asarray(tr.state["w"])) == golden_w
            assert consumed == golden_stream
            assert faulty.transients_injected > 0
            assert mgr.storage.retries >= faulty.transients_injected
            assert mgr.storage.gave_up == 0

    def test_transient_burst_beyond_budget_then_resume(self):
        """A transient burst longer than the retry budget escapes, kills
        the run — and the restart still recovers (transient x mid-save)."""
        golden_w, golden_stream = golden_run()
        with tempfile.TemporaryDirectory() as d:
            faulty = FaultyStorage(NativeStorage(d))
            mgr = CheckpointManager(faulty, PREFIX, keep_last=2,
                                    retry_policy=FAST_RETRY)
            tr = make_trainer(mgr, [])
            tr.run(3)  # checkpoint at step 2 landed
            faulty.transient(n_ops=50, ops=("write",))
            with pytest.raises(TransientFault):
                tr.run(N_STEPS - 3)
            tr.close()
            assert mgr.storage.gave_up >= 1
            faulty.heal()
            finish_from_checkpoint(faulty, golden_w, golden_stream,
                                   ctx="transient burst")


class TestMidDrainKill:
    """Kills inside the burst-buffer drain, recovery from the slow tier
    alone (the node — and its fast tier — is gone)."""

    def _run_with_bb(self, fast, slow, consumed):
        bb = CheckpointManager(slow, PREFIX, engine="bb", fast_storage=fast)
        tr = make_trainer(bb, consumed)
        tr.run(N_STEPS)
        return tr, bb

    def _count_slow_write_ops(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            slow = FaultyStorage(NativeStorage(d2))
            tr, bb = self._run_with_bb(NativeStorage(d1), slow, [])
            tr.wait_for_checkpoints()
            bb.close()
            tr.close()
            return sum(1 for op, _, _ in slow.op_log
                       if op.startswith("write") or op == "append_file")

    def test_drain_killed_at_every_slow_write_op(self):
        golden_w, golden_stream = golden_run()
        n_ops = self._count_slow_write_ops()
        assert n_ops >= 8  # several drains x (data+index+meta+marker)
        for k in range(n_ops):
            with tempfile.TemporaryDirectory() as d1, \
                    tempfile.TemporaryDirectory() as d2:
                slow_inner = NativeStorage(d2)
                slow = FaultyStorage(slow_inner).torn_write(0.5, n_ops=k)
                tr, bb = self._run_with_bb(NativeStorage(d1), slow, [])
                with pytest.raises(FaultInjected):
                    tr.wait_for_checkpoints()
                try:
                    bb.close()
                except FaultInjected:
                    pass  # later drains of the same cascade
                tr.close()
                # fast tier is gone with the node: slow tier must carry a
                # valid step with pipeline position in its meta
                # early k: the fault may predate the first completed drain,
                # in which case a fresh start is the correct recovery
                finish_from_checkpoint(
                    slow_inner, golden_w, golden_stream,
                    ctx=f"drain op {k}/{n_ops}")


# ---------------------------------------------------------------------------
# CheckpointManager: retention, GC, corruption-aware restore
# ---------------------------------------------------------------------------
def small_tree(step: int):
    rng = np.random.default_rng(step)
    return {"w": rng.normal(size=(32,)).astype(np.float32),
            "step": np.int64(step)}


class TestCheckpointManager:
    def test_keep_last_bounds_disk(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=3)
        for s in range(1, 11):
            mgr.save(s, small_tree(s))
        assert mgr.all_steps() == [8, 9, 10]
        names = tmp_storage.listdir("ckpt")
        # 3 steps x (data+index+meta) + marker — nothing strays
        assert len([n for n in names if n != "checkpoint"]) == 9
        assert set(mgr.gc_deleted) == set(range(1, 8))

    def test_keep_every_pins_milestones(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=2,
                                keep_every=5)
        for s in range(1, 13):
            mgr.save(s, small_tree(s))
        assert mgr.all_steps() == [5, 10, 11, 12]

    def test_gc_never_deletes_only_valid_target(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=2)
        trees = {s: small_tree(s) for s in (1, 2, 3)}
        for s in (1, 2, 3):
            mgr.save(s, trees[s])
        assert mgr.all_steps() == [2, 3]
        # newest step torn: the only valid target is now 2, which plain
        # keep_last=1 retention would delete
        tmp_storage.write_file(f"{PREFIX}-3.data-00000-of-00001", b"xx")
        mgr2 = CheckpointManager(tmp_storage, PREFIX, keep_last=1)
        deleted = mgr2.gc()
        assert 2 not in deleted
        assert mgr2.latest_valid() == 2
        flat, _, s = mgr2.restore()
        assert s == 2
        np.testing.assert_array_equal(flat["w"], trees[2]["w"])

    def test_restore_walks_back_past_corruption(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=5)
        trees = {s: small_tree(s) for s in (1, 2, 3)}
        for s in (1, 2, 3):
            mgr.save(s, trees[s])
        # torn shard on 3, truncated meta on 2
        tmp_storage.write_file(f"{PREFIX}-3.data-00000-of-00001", b"torn")
        tmp_storage.write_file(f"{PREFIX}-2.meta", b'{"step"')
        assert mgr.latest_valid() == 1
        flat, meta, s = mgr.restore()
        assert s == 1
        np.testing.assert_array_equal(flat["w"], trees[1]["w"])

    def test_restore_survives_missing_marker(self, tmp_storage):
        """Marker-fallback: candidates come from the directory listing."""
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=5)
        t = small_tree(7)
        mgr.save(7, t)
        tmp_storage.remove("ckpt/checkpoint")
        mgr2 = CheckpointManager(tmp_storage, PREFIX, keep_last=5)
        assert mgr2.latest_valid() == 7
        flat, _, s = mgr2.restore()
        assert s == 7
        np.testing.assert_array_equal(flat["w"], t["w"])

    def test_restore_survives_corrupt_marker(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=5)
        t = small_tree(1)
        mgr.save(1, t)
        tmp_storage.write_file("ckpt/checkpoint", b"{torn j")
        assert latest_valid_step(tmp_storage, PREFIX) == 1
        flat, _, s = mgr.restore()
        assert s == 1

    def test_gc_reclaims_strays_from_interrupted_gc(self, tmp_storage):
        """Files of a step outside the marker (crash between marker rewrite
        and deletion) are swept by the next GC."""
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=2)
        for s in (1, 2, 3):
            mgr.save(s, small_tree(s))
        # simulate a crashed GC that never deleted step 1's files
        saver = CheckpointSaver(tmp_storage, PREFIX)
        saver.save_flat(1, {"w": np.zeros(4, np.float32)})
        assert 1 in list_steps(tmp_storage, PREFIX)
        mgr.gc()
        assert 1 not in list_steps(tmp_storage, PREFIX)

    def test_resume_fresh_when_nothing_saved(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX)
        skeleton = {"w": np.zeros(4)}
        res = mgr.resume(skeleton)
        assert res.fresh and res.step is None
        assert res.state is skeleton

    def test_resume_restores_params_and_iterator(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX)
        t = small_tree(4)
        it = make_iter()
        for _ in range(4):
            next(it)
        mgr.save(4, t, extra_meta={"pipeline": it.state()})
        it2 = make_iter()
        res = mgr.resume(small_tree(0), data_iter=it2)
        assert res.step == 4
        assert res.pipeline == {"epoch": 0, "offset": 4, "version": 1}
        np.testing.assert_array_equal(res.state["w"], t["w"])
        assert float(next(it2)) == float(sample_value(0, 4))

    def test_explicit_step_restore(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=5)
        trees = {s: small_tree(s) for s in (1, 2)}
        for s in (1, 2):
            mgr.save(s, trees[s])
        flat, _, s = mgr.restore(step=1)
        assert s == 1
        np.testing.assert_array_equal(flat["w"], trees[1]["w"])

    def test_validation_args(self, tmp_storage):
        with pytest.raises(ValueError):
            CheckpointManager(tmp_storage, PREFIX, keep_last=0)
        with pytest.raises(ValueError):
            CheckpointManager(tmp_storage, PREFIX, keep_every=0)


class TestParallelRestoreUnderTransients:
    def test_io_threads_restore_with_transient_read_faults(self, tmp_storage):
        """Satellite: parallel-shard restore (io_threads > 1) was only
        tested fault-free — under transient read faults every shard read
        must retry independently and the restore must be bit-identical."""
        faulty = FaultyStorage(tmp_storage)
        rs = RetryingStorage(faulty, FAST_RETRY)
        saver = CheckpointSaver(rs, PREFIX, n_shards=4, io_threads=4)
        rng = np.random.default_rng(0)
        t = {f"w{i}": rng.normal(size=(64, 16)).astype(np.float32)
             for i in range(6)}
        saver.save(1, t)
        # seeded rate spreads faults across the concurrent shard reads
        # (a burst would be absorbed by whichever read hits it first)
        faulty.transient(rate=0.3, ops=("read",), seed=5)
        out = saver.restore_pytree(t)
        for k in t:
            np.testing.assert_array_equal(out[k], t[k])
        assert faulty.transients_injected > 0
        assert rs.retries == faulty.transients_injected and rs.gave_up == 0

    def test_io_threads_restore_gives_up_on_dead_device(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        rs = RetryingStorage(faulty, RetryPolicy(max_attempts=2,
                                                 base_delay_s=1e-5))
        saver = CheckpointSaver(rs, PREFIX, n_shards=4, io_threads=4)
        t = {"w": np.arange(512, dtype=np.float32)}
        saver.save(1, t)
        faulty.fail_after(0, ops=("read",))
        with pytest.raises(FaultInjected):
            saver.restore_pytree(t)
        assert rs.gave_up >= 1


# ---------------------------------------------------------------------------
# pipeline: retry transparency + shard quarantine
# ---------------------------------------------------------------------------
class TestPipelineRetryAndQuarantine:
    def _shards(self, storage, n=3, recs=4):
        names = []
        for i in range(n):
            storage.write_file(f"s{i}", bytes(range(i * recs, (i + 1) * recs)))
            names.append(f"s{i}")
        return names

    def test_transient_reads_absorbed_no_drops(self, tmp_storage):
        names = self._shards(tmp_storage)
        faulty = FaultyStorage(tmp_storage).transient(n_ops=2, ops=("read",))
        rs = RetryingStorage(faulty, FAST_RETRY)

        def stream(name):
            return iter(rs.read_file(name))

        ds = Dataset.from_tensor_slices(names).interleave(
            stream, cycle_length=2).ignore_errors()
        out = sorted(ds.as_numpy())
        assert out == list(range(12))  # nothing dropped, nothing duplicated
        assert rs.retries >= 2 and rs.gave_up == 0

    def test_shard_quarantined_only_after_budget_exhausted(self, tmp_storage):
        from repro import metrics

        names = self._shards(tmp_storage)
        # every read of s1 fails (path-filtered burst), without the device
        # going sticky-dead for the other shards
        faulty = FaultyStorage(tmp_storage).transient(
            n_ops=100, on="s1", ops=("read",))
        rs = RetryingStorage(faulty, RetryPolicy(max_attempts=3,
                                                 base_delay_s=1e-5))

        def stream(name):
            return iter(rs.read_file(name))

        reg = metrics.start()
        try:
            ds = Dataset.from_tensor_slices(names).interleave(
                stream, cycle_length=3).ignore_errors()
            out = sorted(ds.as_numpy())
            # s1's records are gone (quarantined), the rest all survive
            assert out == list(range(0, 4)) + list(range(8, 12))
            counters = reg.collect()["counters"]
            quarantined = sum(v for k, v in counters.items()
                              if k.startswith("pipeline.quarantined_shards"))
            assert quarantined == 1
        finally:
            metrics.stop()
        assert rs.gave_up == 1  # the drop happened only after the budget


class TestImagePipelineResume:
    def test_asyncbb_preempt_mid_epoch_resumes_by_seek(self):
        """A factory with no start offset, as a training job writes it:
        the resume seeks the image pipeline (no replay) and the resumed
        run neither skips nor repeats a sample, across the epoch's end."""
        from repro import metrics
        from repro.core import records, sharded_image_pipeline
        from repro.train.trainer import Trainer

        with tempfile.TemporaryDirectory() as d0, \
                tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            corpus = NativeStorage(d0)
            paths, labels = records.write_sharded_image_dataset(
                corpus, 40, 6, mean_hw=(12, 12), hw_jitter=0.0, seed=1)

            def epoch(ep):
                return sharded_image_pipeline(
                    corpus, paths, labels, batch_size=4, cycle_length=3,
                    block_length=4, num_parallel_calls=2, out_hw=(8, 8),
                    seed=ep)

            golden = list(ResumableIterator(epoch, epochs=2))  # 2 x 10
            seen = []

            def step(state, batch):
                seen.append(batch)
                return ({"w": state["w"] + np.float64(1.0),
                         "step": state["step"] + np.int64(1)},
                        {"loss": np.float64(0.0)})

            def manager():
                return CheckpointManager(
                    NativeStorage(d2), PREFIX, engine="asyncbb",
                    fast_storage=NativeStorage(d1), max_pending=2)

            state = {"w": np.float64(0.0), "step": np.int64(0)}
            mgr = manager()
            tr = Trainer(step, state, ResumableIterator(epoch, epochs=2),
                         checkpointer=mgr, ckpt_every=3, resume=False)
            tr.run(4)
            tr.ckpt_every = 0           # the stop makes the fifth's save
            tr.preempt(30.0)
            tr.run(1)
            tr.close()
            mgr.close()
            assert len(seen) == 5

            reg = metrics.start()
            try:
                mgr2 = manager()
                tr2 = Trainer(step, state, ResumableIterator(epoch, epochs=2),
                              checkpointer=mgr2, resume=True)
                counters = reg.collect()["counters"]
            finally:
                metrics.stop()
            assert tr2.recovered_step == 5
            assert sum(v for k, v in counters.items()
                       if k.startswith("pipeline.resume_seeks")) == 1
            assert not any(k.startswith("pipeline.resume_skipped")
                           for k in counters)
            tr2.run(20)                 # to the end of the second epoch
            tr2.close()
            mgr2.close()
        assert len(seen) == len(golden) == 20
        for (a_img, a_lbl), (b_img, b_lbl) in zip(seen, golden):
            np.testing.assert_array_equal(a_img, b_img)
            np.testing.assert_array_equal(a_lbl, b_lbl)


# ---------------------------------------------------------------------------
# ResumableIterator semantics
# ---------------------------------------------------------------------------
class TestResumableIterator:
    def test_epoch_rollover_and_bounded_epochs(self):
        it = ResumableIterator(lambda ep: Dataset.from_tensor_slices(
            [sample_value(ep, i) for i in range(3)]), epochs=2)
        vals = [float(v) for v in it]
        assert vals == [1.0, 2.0, 3.0, 1001.0, 1002.0, 1003.0]
        assert it.state() == {"epoch": 2, "offset": 0, "version": 1}

    def test_state_counts_delivered_not_prefetched(self):
        ds = Dataset.from_tensor_slices(list(range(10))).prefetch(4)
        it = ResumableIterator(ds)
        for _ in range(3):
            next(it)
        # prefetch buffer is ahead, but only 3 elements were delivered
        assert it.state()["offset"] == 3
        it.close()

    def test_restore_mid_epoch_resumes_exact_element(self):
        def factory(ep):
            return Dataset.from_tensor_slices(
                [sample_value(ep, i) for i in range(5)])

        it = ResumableIterator(factory)
        got = [float(next(it)) for _ in range(7)]
        st = it.state()
        it2 = ResumableIterator(factory)
        it2.restore_state(st)
        tail = [float(next(it2)) for _ in range(3)]
        more = [float(next(it)) for _ in range(3)]
        assert tail == more
        it.close(), it2.close()

    def test_restore_replays_per_epoch_shuffle_order(self):
        """A seeded-per-epoch shuffle factory must resume onto the exact
        same shuffled order (the factory rebuilds epoch e from its seed)."""
        def factory(ep):
            return Dataset.from_tensor_slices(
                list(range(8))).shuffle(8, seed=100 + ep)

        it = ResumableIterator(factory)
        [next(it) for _ in range(11)]  # 3 elements into epoch 1
        st = it.state()
        it2 = ResumableIterator(factory)
        it2.restore_state(st)
        assert [next(it2) for _ in range(5)] == [next(it) for _ in range(5)]
        # epoch 1's order actually differs from epoch 0's (seed moved)
        assert list(factory(0)) != list(factory(1))
        it.close(), it2.close()

    def test_restore_past_end_rolls_into_next_epoch(self):
        factory = lambda ep: Dataset.from_tensor_slices([ep * 10, ep * 10 + 1])
        it = ResumableIterator(factory)
        it.restore_state({"epoch": 0, "offset": 2, "version": 1})
        assert next(it) == 10  # epoch 0 exhausted by the skip -> epoch 1

    def test_empty_source_terminates(self):
        it = ResumableIterator(Dataset.from_tensor_slices([]))
        with pytest.raises(StopIteration):
            next(it)

    def test_dataset_source_repeats_same_order(self):
        it = ResumableIterator(Dataset.from_tensor_slices([1, 2]), epochs=3)
        assert list(it) == [1, 2, 1, 2, 1, 2]

    def test_rejects_non_dataset_source(self):
        with pytest.raises(TypeError):
            ResumableIterator([1, 2, 3])

    def test_context_manager_closes(self):
        ds = Dataset.from_tensor_slices(list(range(4))).prefetch(2)
        with ResumableIterator(ds) as it:
            next(it)
        assert it._it is None


# ---------------------------------------------------------------------------
# fused manager (PR 10): lifecycle states, deferred GC, dual-tier restore
# ---------------------------------------------------------------------------
def _wait_until(cond, timeout=10.0):
    import time

    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.005)
    return True


class TestFusedManager:
    def test_lifecycle_states_direct_engine(self, tmp_storage):
        from repro.core.recovery import COMMITTED

        mgr = CheckpointManager(tmp_storage, PREFIX)
        mgr.save(1, small_tree(1))
        assert mgr.step_states()[1] == COMMITTED

    def test_lifecycle_states_async_engine(self, tmp_storage):
        from repro.core.recovery import COMMITTED

        mgr = CheckpointManager(tmp_storage, PREFIX, engine="async")
        mgr.save(1, small_tree(1))
        mgr.wait()
        assert mgr.step_states()[1] == COMMITTED
        mgr.close()

    def test_gc_deferred_past_drain_commit(self):
        """Retention must never collect a step staged on the fast tier but
        not yet drained — it is the preemption-restart target."""
        from repro.core.recovery import COMMITTED, STAGED

        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast = NativeStorage(d1)
            slow = FaultyStorage(NativeStorage(d2))
            mgr = CheckpointManager(slow, PREFIX, engine="asyncbb",
                                    fast_storage=fast, keep_last=2,
                                    max_pending=2)
            slow.hang(on=".data-", repeat=True)  # drains wedge forever
            trees = {s: small_tree(s) for s in range(1, 6)}
            for s in range(1, 6):
                mgr.save(s, trees[s])
            assert _wait_until(lambda: mgr.engine.pending() == 0)
            # every step staged, none drained: GC has never run.  (Partial
            # slow-tier files may exist — index/meta chunks drain on other
            # streams — but nothing validates and nothing was collected.)
            states = mgr.step_states()
            assert all(states[s] == STAGED for s in range(1, 6))
            assert valid_steps(slow, PREFIX) == []
            assert mgr.gc_deleted == []
            assert mgr.valid_steps() == [1, 2, 3, 4, 5]  # fast tier carries
            assert mgr.latest_valid() == 5
            flat, _, s = mgr.restore()
            assert s == 5
            np.testing.assert_array_equal(flat["w"], trees[5]["w"])
            # un-wedge: drains commit in order, deferred GC kicks in
            slow.heal()
            mgr.wait()
            assert mgr.step_states()[5] == COMMITTED
            assert mgr.all_steps() == [4, 5]  # keep_last applied, at last
            assert set(mgr.gc_deleted) == {1, 2, 3}
            mgr.close()

    def test_restore_falls_back_when_fast_tier_corrupt(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast, slow = NativeStorage(d1), NativeStorage(d2)
            mgr = CheckpointManager(slow, PREFIX, engine="bb",
                                    fast_storage=fast, keep_last=3)
            t = small_tree(1)
            mgr.save(1, t)
            mgr.wait()
            # fast copy torn after the drain: restore must take the slow one
            fast.write_file(f"{PREFIX}-1.data-00000-of-00001", b"xx")
            flat, _, s = mgr.restore()
            assert s == 1
            np.testing.assert_array_equal(flat["w"], t["w"])
            mgr.close()

    def test_close_idempotent_and_error_exactly_once(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage).fail_on(".data-")
        mgr = CheckpointManager(faulty, PREFIX, engine="async")
        mgr.save(1, small_tree(1))  # background write will die
        with pytest.raises(FaultInjected):
            mgr.close()
        mgr.close()  # second close: no-op, the error was delivered once
        with pytest.raises(RuntimeError):
            mgr.save(2, small_tree(2))

    def test_close_with_pending_saves_drains_them(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast, slow = NativeStorage(d1), NativeStorage(d2)
            mgr = CheckpointManager(slow, PREFIX, engine="asyncbb",
                                    fast_storage=fast, keep_last=3)
            for s in (1, 2, 3):
                mgr.save(s, small_tree(s))
            mgr.close()  # drains the stager and the drain queue
            mgr.close()  # idempotent
            assert latest_valid_step(slow, PREFIX) == 3

    def test_blocked_s_comes_from_engine(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, engine="async")
        mgr.save(1, small_tree(1))
        mgr.wait()
        assert mgr.blocked_s is mgr.engine.blocked_s
        assert len(mgr.blocked_s) == 1
        mgr.close()

    def test_engine_validation(self, tmp_storage):
        with pytest.raises(ValueError):
            CheckpointManager(tmp_storage, PREFIX, engine="warp")
        with pytest.raises(ValueError):
            CheckpointManager(tmp_storage, PREFIX, engine="asyncbb")


# ---------------------------------------------------------------------------
# satellite: kill sweep through the fused manager + asyncbb engine
# ---------------------------------------------------------------------------
class TestFusedKillSweep:
    """The TestKillSweep guarantee, re-proven through the fused
    manager+asyncbb save/drain/GC path: die (or wedge) at every slow-tier
    write op and the restart — on a fresh node with an empty fast tier —
    still lands bit-identical params with no skipped/replayed samples."""

    def _fused_mgr(self, fast, slow, **kw):
        kw.setdefault("keep_last", 2)
        return CheckpointManager(slow, PREFIX, engine="asyncbb",
                                 fast_storage=fast, **kw)

    def _finish_fused(self, slow_storage, golden_w, golden_stream, ctx=""):
        """Restart on a fresh node: empty fast tier, healed slow tier."""
        with tempfile.TemporaryDirectory() as d_fast:
            mgr = self._fused_mgr(NativeStorage(d_fast), slow_storage)
            consumed = []
            tr = make_trainer(mgr, consumed)
            start = tr.recovered_step or 0
            tr.run(N_STEPS - start)
            tr.wait_for_checkpoints()
            mgr.close()
            tr.close()
            assert float(np.asarray(tr.state["w"])) == golden_w, ctx
            assert consumed == golden_stream[start:], ctx
            return start

    def _count_slow_write_ops(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            slow = FaultyStorage(NativeStorage(d2))
            mgr = self._fused_mgr(NativeStorage(d1), slow)
            tr = make_trainer(mgr, [])
            tr.run(N_STEPS)
            tr.wait_for_checkpoints()
            mgr.close()
            tr.close()
            return sum(1 for op, _, _ in slow.op_log
                       if op.startswith("write") or op == "append_file")

    @pytest.mark.parametrize("model", ["clean", "torn"])
    def test_kill_at_every_slow_write_op(self, model):
        golden_w, golden_stream = golden_run()
        n_ops = self._count_slow_write_ops()
        assert n_ops >= 8  # drain chunks + markers + GC marker rewrites
        for k in range(n_ops):
            with tempfile.TemporaryDirectory() as d1, \
                    tempfile.TemporaryDirectory() as d2:
                slow_inner = NativeStorage(d2)
                slow = FaultyStorage(slow_inner)
                if model == "clean":
                    slow.fail_after(k)
                else:
                    slow.torn_write(0.5, n_ops=k)
                mgr = self._fused_mgr(NativeStorage(d1), slow)
                tr = make_trainer(mgr, [])
                tr.run(N_STEPS)  # stages are fast-tier: the run completes
                with pytest.raises(FaultInjected):
                    tr.wait_for_checkpoints()  # the drain error surfaces
                try:
                    mgr.close()
                except FaultInjected:
                    pass  # later drains of the same sticky cascade
                tr.close()
                self._finish_fused(slow_inner, golden_w, golden_stream,
                                   ctx=f"model={model}, op {k}/{n_ops}")

    def test_reordered_fsync_crash_on_slow_tier(self):
        golden_w, golden_stream = golden_run()
        for j in (2, 4, N_STEPS - 1):
            for keep in ("last", "none"):
                with tempfile.TemporaryDirectory() as d1, \
                        tempfile.TemporaryDirectory() as d2:
                    slow_inner = NativeStorage(d2)
                    slow = FaultyStorage(slow_inner).reordered_fsync()
                    mgr = self._fused_mgr(NativeStorage(d1), slow)
                    tr = make_trainer(mgr, [])
                    tr.run(j)
                    tr.wait_for_checkpoints()
                    mgr.close()
                    tr.close()
                    slow.crash(keep=keep)  # power loss: volatile writes gone
                    slow.heal()
                    self._finish_fused(
                        slow_inner, golden_w, golden_stream,
                        ctx=f"crash(keep={keep}) after {j}")

    def test_hung_drain_absorbed_by_watchdog(self):
        """A wedged (not dead) slow tier mid-run: the watchdog re-queues
        the chunk and the run itself completes bit-identical — no restart
        needed at all."""
        golden_w, golden_stream = golden_run()
        for arm in ({"on": ".data-"}, {"n_ops": 2, "ops": ("write_range",)}):
            with tempfile.TemporaryDirectory() as d1, \
                    tempfile.TemporaryDirectory() as d2:
                slow = FaultyStorage(NativeStorage(d2))
                mgr = self._fused_mgr(NativeStorage(d1), slow,
                                      drain_stall_timeout=0.1,
                                      drain_streams=2, drain_chunk=64)
                slow.hang(**arm)  # one-shot wedge: the re-queue succeeds
                consumed = []
                tr = make_trainer(mgr, consumed)
                tr.run(N_STEPS)
                tr.wait_for_checkpoints()
                assert mgr.engine.drain_stalls >= 1, arm
                assert float(np.asarray(tr.state["w"])) == golden_w
                assert consumed == golden_stream
                slow.heal()  # un-park the leaked stream
                mgr.close()
                tr.close()
