"""The checkpoint engine's storage traffic, pinned, and the Trainer's one
checkpointer interface.

Golden sequences: every storage operation each ``CheckpointManager`` preset
issues for ``save(1); wait(); save(2); wait(); close()`` with
``keep_last=1``, per tier, as ``"<op> <path>"`` (ranges as ``path@offset``,
renames as ``src->dst``).  They pin the files written, their order, the
slow-tier marker published by the drain and then rewritten by the manager's
GC, the fast-tier cleanup and the deletions.  They were recorded from the
four separate engine classes that ``CheckpointEngine`` replaced, so the one
engine is held to their storage traffic.  One shard-write and one drain
stream keep the order deterministic; a ``wait()`` after each save keeps the
two background threads of the two-tier presets from interleaving.
"""
import tempfile

import numpy as np
import pytest

from repro.core.recovery import ENGINES, CheckpointManager
from repro.core.storage import NativeStorage, Storage

PREFIX = "ckpt/m"
PRESETS = tuple(ENGINES)


def _ops(text):
    return [line.strip() for line in text.strip().splitlines()]


ONE_TIER = _ops("""
    makedirs ckpt
    makedirs ckpt
    write_file ckpt/m-1.data-00000-of-00002
    write_file ckpt/m-1.data-00001-of-00002
    write_file ckpt/m-1.index
    write_file ckpt/m-1.meta
    fsync_dir ckpt
    exists ckpt/checkpoint
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    listdir ckpt
    listdir ckpt
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    write_file ckpt/m-2.data-00000-of-00002
    write_file ckpt/m-2.data-00001-of-00002
    write_file ckpt/m-2.index
    write_file ckpt/m-2.meta
    fsync_dir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    listdir ckpt
    listdir ckpt
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    read_file ckpt/m-2.meta
    read_file ckpt/m-2.index
    size ckpt/m-2.data-00000-of-00002
    size ckpt/m-2.data-00001-of-00002
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    read_file ckpt/m-2.meta
    read_file ckpt/m-2.index
    size ckpt/m-2.data-00000-of-00002
    size ckpt/m-2.data-00001-of-00002
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    listdir ckpt
    remove ckpt/m-1.data-00000-of-00002
    remove ckpt/m-1.data-00001-of-00002
    remove ckpt/m-1.index
    remove ckpt/m-1.meta
""")

TWO_TIER_FAST = _ops("""
    makedirs ckpt
    write_file ckpt/m-1.data-00000-of-00002
    write_file ckpt/m-1.data-00001-of-00002
    write_file ckpt/m-1.index
    write_file ckpt/m-1.meta
    fsync_dir ckpt
    exists ckpt/checkpoint
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    size ckpt/m-1.index
    size ckpt/m-1.meta
    read_range ckpt/m-1.data-00000-of-00002@0
    read_range ckpt/m-1.data-00000-of-00002@4096
    read_range ckpt/m-1.data-00001-of-00002@0
    read_range ckpt/m-1.index@0
    read_range ckpt/m-1.meta@0
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    write_file ckpt/m-2.data-00000-of-00002
    write_file ckpt/m-2.data-00001-of-00002
    write_file ckpt/m-2.index
    write_file ckpt/m-2.meta
    fsync_dir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    size ckpt/m-2.data-00000-of-00002
    size ckpt/m-2.data-00001-of-00002
    size ckpt/m-2.index
    size ckpt/m-2.meta
    read_range ckpt/m-2.data-00000-of-00002@0
    read_range ckpt/m-2.data-00000-of-00002@4096
    read_range ckpt/m-2.data-00001-of-00002@0
    read_range ckpt/m-2.index@0
    read_range ckpt/m-2.meta@0
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    listdir ckpt
    remove ckpt/m-1.data-00000-of-00002
    remove ckpt/m-1.data-00001-of-00002
    remove ckpt/m-1.index
    remove ckpt/m-1.meta
""")

TWO_TIER_SLOW = _ops("""
    makedirs ckpt
    makedirs ckpt
    write_range ckpt/m-1.data-00000-of-00002@0
    write_range ckpt/m-1.data-00000-of-00002@4096
    write_range ckpt/m-1.data-00001-of-00002@0
    write_range ckpt/m-1.index@0
    write_range ckpt/m-1.meta@0
    exists ckpt/checkpoint
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    listdir ckpt
    listdir ckpt
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    write_range ckpt/m-2.data-00000-of-00002@0
    write_range ckpt/m-2.data-00000-of-00002@4096
    write_range ckpt/m-2.data-00001-of-00002@0
    write_range ckpt/m-2.index@0
    write_range ckpt/m-2.meta@0
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    listdir ckpt
    listdir ckpt
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    read_file ckpt/m-2.meta
    read_file ckpt/m-2.index
    size ckpt/m-2.data-00000-of-00002
    size ckpt/m-2.data-00001-of-00002
    listdir ckpt
    exists ckpt/checkpoint
    read_file ckpt/checkpoint
    read_file ckpt/m-1.meta
    read_file ckpt/m-1.index
    size ckpt/m-1.data-00000-of-00002
    size ckpt/m-1.data-00001-of-00002
    read_file ckpt/m-2.meta
    read_file ckpt/m-2.index
    size ckpt/m-2.data-00000-of-00002
    size ckpt/m-2.data-00001-of-00002
    write_file ckpt/checkpoint.tmp
    rename ckpt/checkpoint.tmp->ckpt/checkpoint
    listdir ckpt
    remove ckpt/m-1.data-00000-of-00002
    remove ckpt/m-1.data-00001-of-00002
    remove ckpt/m-1.index
    remove ckpt/m-1.meta
""")

GOLDEN = {
    "direct": ([], ONE_TIER),
    "async": ([], ONE_TIER),
    "bb": (TWO_TIER_FAST, TWO_TIER_SLOW),
    "asyncbb": (TWO_TIER_FAST, TWO_TIER_SLOW),
}


class Recording(Storage):
    """Pass-through storage that logs every operation it is asked for."""

    def __init__(self, inner):
        self.inner = inner
        self.ops = []

    def read_file(self, path):
        self.ops.append(f"read_file {path}")
        return self.inner.read_file(path)

    def read_range(self, path, offset, length):
        self.ops.append(f"read_range {path}@{offset}")
        return self.inner.read_range(path, offset, length)

    def write_file(self, path, data, sync=False):
        self.ops.append(f"write_file {path}")
        self.inner.write_file(path, data, sync=sync)

    def append_file(self, path, data, sync=False):
        self.ops.append(f"append_file {path}")
        self.inner.append_file(path, data, sync=sync)

    def write_range(self, path, offset, data, sync=False):
        self.ops.append(f"write_range {path}@{offset}")
        self.inner.write_range(path, offset, data, sync=sync)

    def fsync_dir(self, path):
        self.ops.append(f"fsync_dir {path}")
        self.inner.fsync_dir(path)

    def listdir(self, path):
        self.ops.append(f"listdir {path}")
        return self.inner.listdir(path)

    def exists(self, path):
        self.ops.append(f"exists {path}")
        return self.inner.exists(path)

    def makedirs(self, path):
        self.ops.append(f"makedirs {path}")
        self.inner.makedirs(path)

    def remove(self, path):
        self.ops.append(f"remove {path}")
        self.inner.remove(path)

    def rename(self, src, dst):
        self.ops.append(f"rename {src}->{dst}")
        self.inner.rename(src, dst)

    def size(self, path):
        self.ops.append(f"size {path}")
        return self.inner.size(path)


@pytest.mark.parametrize("preset", PRESETS)
def test_storage_ops_match_golden(preset):
    tree = {"w": np.arange(2048, dtype=np.float32),
            "b": np.ones(8, np.float32)}
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        fast = Recording(NativeStorage(d1))
        slow = Recording(NativeStorage(d2))
        mgr = CheckpointManager(slow, PREFIX, engine=preset,
                                fast_storage=fast, keep_last=1, n_shards=2,
                                io_threads=1, drain_streams=1,
                                drain_chunk=4096)
        for step in (1, 2):
            mgr.save(step, tree)
            mgr.wait()
        mgr.close()
    want_fast, want_slow = GOLDEN[preset]
    assert fast.ops == want_fast
    assert slow.ops == want_slow


# ---------------------------------------------------------------------------
# the Trainer's checkpointer interface
# ---------------------------------------------------------------------------
class OnlyInterface:
    """Delegates the five names the Trainer may use, and nothing else (the
    shape of a proxy that forwards through ``__getattr__``)."""

    NAMES = ("resume", "save", "preempt", "wait", "blocked_s")

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name not in self.NAMES:
            raise AttributeError(name)
        return getattr(self._inner, name)


def _stream():
    from repro.core.dataset import Dataset, ResumableIterator

    consumed = []

    def step_fn(state, batch):
        consumed.append(float(batch))
        return ({"w": state["w"] * 0.5 + batch,
                 "step": state["step"] + np.int64(1)}, {"loss": batch})

    data = ResumableIterator(lambda ep: Dataset.from_tensor_slices(
        [np.float64(ep * 100 + i + 1) for i in range(8)]))
    state = {"w": np.float64(0.0), "step": np.int64(0)}
    return state, step_fn, data, consumed


@pytest.mark.parametrize("preset", PRESETS)
def test_trainer_needs_no_probe(preset):
    """Save, stop, preempt and resume through a proxy that only delegates."""
    from repro.train.trainer import Trainer

    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        fast, slow = NativeStorage(d1), NativeStorage(d2)

        def manager():
            return CheckpointManager(slow, PREFIX, engine=preset,
                                     fast_storage=fast, keep_last=3)

        mgr = manager()
        state, step_fn, data, consumed = _stream()
        tr = Trainer(step_fn, state, data, checkpointer=OnlyInterface(mgr),
                     ckpt_every=2)
        assert tr.recovered_step is None
        tr.on_step = lambda step, m: step == 3 and tr.preempt(10.0)
        tr.run(6)
        assert consumed == [1.0, 2.0, 3.0]
        assert tr.preemption_report.committed_step == 3
        assert tr.preemption_report.deadline_met
        tr.wait_for_checkpoints()
        assert len(tr.report()["blocked_ckpt_s"]) == 2  # steps 2 and 3
        mgr.close()

        mgr2 = manager()
        state, step_fn, data, consumed = _stream()
        tr2 = Trainer(step_fn, state, data, checkpointer=OnlyInterface(mgr2),
                      ckpt_every=2)
        assert tr2.recovered_step == 3
        tr2.run(3)
        assert consumed == [4.0, 5.0, 6.0]
        tr2.wait_for_checkpoints()
        mgr2.close()
