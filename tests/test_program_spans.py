"""Spans inside the step, the save, the stop, the resume and the input
pipeline: a SMOKE-width ``Trainer`` over a ``ResumableIterator`` of the
sharded image pipeline (Pallas resize, interpreted on the CPU), saving
through an asyncbb ``CheckpointManager``, preempted, then resumed."""
import gc
import os
import threading
import tracemalloc

import jax
import jax.numpy as jnp
import pytest

from repro import trace
from repro.configs import ALEXNET_SMOKE as CFG
from repro.core import (CheckpointManager, ResumableIterator, make_storage,
                        records, sharded_image_pipeline)
from repro.models import alexnet
from repro.train.trainer import Trainer

BATCH = 8
HW = 32
N_IMAGES = 32          # 4 batches an epoch
STEPS = 5              # steps 1-5: saves at 2 and 4, epoch 1 opens at 5
CKPT_EVERY = 2


def _pipeline(corpus):
    storage, paths, labels = corpus

    def epoch(ep):
        return sharded_image_pipeline(
            storage, paths, labels, batch_size=BATCH,
            out_hw=(CFG.in_hw, CFG.in_hw), batched_preprocess="pallas",
            seed=ep, repeat=False)

    return ResumableIterator(epoch)


def _manager(root):
    return CheckpointManager(
        make_storage("native", os.path.join(root, "slow")), "ckpt/m",
        engine="asyncbb", fast_storage=make_storage(
            "native", os.path.join(root, "fast")))


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Spans of: 5 steps with two periodic saves, a preempted sixth step,
    the close, a resume and one resumed step.  Returns (spans, training
    thread id, the first trainer's report)."""
    root = str(tmp_path_factory.mktemp("spans"))
    storage = make_storage("native", os.path.join(root, "corpus"))
    paths, labels = records.write_sharded_image_dataset(
        storage, N_IMAGES, BATCH, mean_hw=(HW, HW), hw_jitter=0,
        n_classes=CFG.n_classes, seed=0)
    corpus = (storage, paths, labels)
    step_fn = alexnet.make_train_step(CFG)
    state = {"params": alexnet.init_params(jax.random.PRNGKey(0), CFG),
             "step": jnp.int32(0)}
    tracer = trace.start()
    try:
        mgr = _manager(root)
        tr = Trainer(step_fn, state, _pipeline(corpus), checkpointer=mgr,
                     ckpt_every=CKPT_EVERY, resume=False)
        tr.run(STEPS)
        tr.ckpt_every = 0      # the stop makes the sixth step's save
        tr.preempt(30.0)
        tr.run(1)
        tr.close()
        mgr.close()
        report = tr.report()
        mgr2 = _manager(root)
        tr2 = Trainer(step_fn, jax.eval_shape(lambda: state),
                      _pipeline(corpus), checkpointer=mgr2, resume=True)
        assert tr2.recovered_step == STEPS + 1
        tr2.run(1)
        tr2.close()
        mgr2.close()
    finally:
        trace.stop()
    return tracer.spans(), threading.get_ident(), report


def _of(spans, stage, name=None, tid=None):
    return [s for s in spans if s.stage == stage
            and (name is None or s.name == name)
            and (tid is None or s.tid == tid)]


def _inside(inner, outer):
    return (inner.tid == outer.tid and outer.t0 <= inner.t0
            and inner.t0 + inner.dur <= outer.t0 + outer.dur)


@pytest.mark.parametrize("stage,name,count", [
    (trace.STAGE_STEP_DISPATCH, "train_step", STEPS + 2),
    (trace.STAGE_STEP_SYNC, "metrics", STEPS + 2),
    (trace.STAGE_STEP_SYNC, "step_counter", STEPS + 2),
    (trace.STAGE_CKPT_SAVE, "ckpt_save", 3),          # steps 2, 4, 6
    (trace.STAGE_PIPELINE_STATE, "pipeline_state", 3),
    (trace.STAGE_CKPT_BACKPRESSURE, "ckpt_backpressure", 3),
    (trace.STAGE_PREEMPT, "preempt", 1),
    (trace.STAGE_PREEMPT_PROMOTE, "preempt_promote", 1),
    (trace.STAGE_PIPELINE_CLOSE, "pipeline_close", 2),
    (trace.STAGE_CKPT_CLOSE, "ckpt_close", 2),
    (trace.STAGE_CKPT_VALIDATE, "ckpt_validate", 1),
    (trace.STAGE_ITERATOR_SEEK, "iterator_seek", 1),
    (trace.STAGE_EPOCH_OPEN, "epoch_open", 2),        # epochs 0 and 1
])
def test_one_span_per_event_on_the_training_thread(run, stage, name, count):
    spans, tid, _ = run
    assert len(_of(spans, stage, name, tid)) == count
    assert len(_of(spans, stage, name)) == count   # and on no other thread


def test_step_spans_nest_in_the_compute_span(run):
    spans, tid, _ = run
    steps = _of(spans, trace.STAGE_COMPUTE, "train_step", tid)
    assert len(steps) == STEPS + 2
    for s in _of(spans, trace.STAGE_STEP_DISPATCH, tid=tid) + \
            _of(spans, trace.STAGE_STEP_SYNC, "metrics", tid):
        assert sum(_inside(s, c) for c in steps) == 1
    # the counter's read follows the step, outside it
    for s in _of(spans, trace.STAGE_STEP_SYNC, "step_counter", tid):
        assert not any(_inside(s, c) for c in steps)
    # dispatch ends before the sync starts, in every step
    for c in steps:
        d, = [s for s in _of(spans, trace.STAGE_STEP_DISPATCH) if _inside(s, c)]
        m, = [s for s in _of(spans, trace.STAGE_STEP_SYNC, "metrics")
              if _inside(s, c)]
        assert d.t0 + d.dur <= m.t0


def test_save_spans_nest_in_ckpt_save(run):
    spans, tid, _ = run
    saves = _of(spans, trace.STAGE_CKPT_SAVE, tid=tid)
    for stage in (trace.STAGE_PIPELINE_STATE, trace.STAGE_CKPT_BACKPRESSURE,
                  trace.STAGE_CKPT_SNAPSHOT):
        inner = _of(spans, stage, tid=tid)
        assert len(inner) == len(saves) == 3
        assert all(sum(_inside(s, o) for o in saves) == 1 for s in inner)


def test_stop_spans_nest_in_preempt(run):
    spans, tid, _ = run
    pre, = _of(spans, trace.STAGE_PREEMPT, tid=tid)
    promote, = _of(spans, trace.STAGE_PREEMPT_PROMOTE, tid=tid)
    last_save = max(_of(spans, trace.STAGE_CKPT_SAVE), key=lambda s: s.t0)
    assert _inside(promote, pre) and _inside(last_save, pre)
    assert last_save.t0 + last_save.dur <= promote.t0
    # the close comes after the stop, outside it
    first_close = min(_of(spans, trace.STAGE_PIPELINE_CLOSE),
                      key=lambda s: s.t0)
    assert first_close.t0 >= pre.t0 + pre.dur


def test_resume_spans_in_order(run):
    spans, tid, _ = run
    validate, = _of(spans, trace.STAGE_CKPT_VALIDATE, tid=tid)
    seek, = _of(spans, trace.STAGE_ITERATOR_SEEK, tid=tid)
    restores = [s for s in _of(spans, trace.STAGE_CKPT_RESTORE, tid=tid)
                if validate.t0 <= s.t0 <= seek.t0]
    assert len(restores) == 1
    assert validate.t0 + validate.dur <= restores[0].t0
    assert restores[0].t0 + restores[0].dur <= seek.t0
    # the seek places the pipeline past the two batches of epoch 1 the
    # preempted run took, without replaying them
    placed, = _of(spans, trace.STAGE_DATA_WAIT, tid=tid,
                  name="resume_seek:2@epoch1")
    assert _inside(placed, seek)
    assert not [s for s in spans if s.name.startswith("resume_skip")]


def test_device_preprocess_carries_the_batch_bytes(run):
    spans, tid, _ = run
    pre = _of(spans, trace.STAGE_DEVICE_PREPROCESS)
    # every step's batch went through it, on the pipeline's threads
    assert len(pre) >= STEPS + 2
    assert all(s.tid != tid and s.name == "pallas" for s in pre)
    assert {s.nbytes for s in pre} == {BATCH * HW * HW * CFG.channels}


def test_trainer_timer_splits_compute(run):
    timer = run[2]["timer"]
    assert {"data_wait", "dispatch", "sync", "compute"} <= set(timer)
    for k in ("dispatch", "sync"):
        assert 0 < timer[k]["total"] <= timer["compute"]["total"]
    assert timer["dispatch"]["total"] + timer["sync"]["total"] \
        <= timer["compute"]["total"]


class TestGcHook:
    def test_installed_only_between_start_and_stop(self):
        before = list(gc.callbacks)
        tracer = trace.start()
        try:
            added = [c for c in gc.callbacks if c not in before]
            assert len(added) == 1
            gc.collect()
        finally:
            trace.stop()
        assert gc.callbacks == before
        spans = _of(tracer.spans(), trace.STAGE_GC, "gc",
                    threading.get_ident())
        assert any(s.args == {"generation": 2} for s in spans)

    def test_set_tracer_removes_the_hook(self):
        before = list(gc.callbacks)
        trace.start()
        trace.set_tracer(None)
        assert gc.callbacks == before
        assert trace.get_tracer() is None

    def test_nothing_recorded_while_disabled(self):
        tracer = trace.start()
        try:
            tracer.disable()
            gc.collect()
        finally:
            trace.stop()
        assert _of(tracer.spans(), trace.STAGE_GC) == []


def test_disabled_step_spans_allocate_nothing():
    """With tracing off the spans a step, a save and an epoch add cost one
    global check each and allocate nothing."""
    assert trace.get_tracer() is None
    before = list(gc.callbacks)

    def burn(n):
        for _ in range(n):
            with trace.span(trace.STAGE_COMPUTE, "train_step"):
                with trace.span(trace.STAGE_STEP_DISPATCH, "train_step"):
                    pass
                with trace.span(trace.STAGE_STEP_SYNC, "metrics"):
                    pass
            with trace.span(trace.STAGE_STEP_SYNC, "step_counter"):
                pass
            with trace.span(trace.STAGE_CKPT_SAVE, "ckpt_save"):
                with trace.span(trace.STAGE_CKPT_BACKPRESSURE,
                                "ckpt_backpressure"):
                    pass
            with trace.span(trace.STAGE_EPOCH_OPEN, "epoch_open"):
                pass
            with trace.span(trace.STAGE_DEVICE_PREPROCESS, "pallas", 24576):
                pass

    burn(100)
    tracemalloc.start()
    burn(10_000)
    _current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 16_384, f"disabled tracing allocated {peak} bytes"
    assert gc.callbacks == before

