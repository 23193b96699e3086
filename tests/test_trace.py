"""repro.trace subsystem: collector, nesting, fast path, export, report."""
import json
import threading
import time
import tracemalloc

import pytest

from repro import trace
from repro.trace import report as trace_report
from repro.trace.tracer import NULL_SPAN, SpanRecord, Tracer


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Each test starts and ends with tracing uninstalled."""
    trace.set_tracer(None)
    yield
    trace.set_tracer(None)


def mkspan(stage, t0, dur, tid=1, nbytes=0, name=""):
    return SpanRecord(stage=stage, name=name, tid=tid, thread=f"t{tid}",
                      t0=t0, dur=dur, nbytes=nbytes)


# ---------------------------------------------------------------------------
# collector
# ---------------------------------------------------------------------------
class TestTracer:
    def test_span_records_stage_bytes_duration(self):
        tr = Tracer()
        with tr.span("storage_read", "f.bin") as sp:
            sp.set_bytes(123)
        (r,) = tr.spans()
        assert r.stage == "storage_read"
        assert r.name == "f.bin"
        assert r.nbytes == 123
        assert r.dur >= 0.0
        assert r.tid == threading.get_ident()

    def test_nesting_across_threads(self):
        """Each thread's inner span must lie inside its own outer span, and
        spans must carry the recording thread's id."""
        tr = Tracer()

        def work(i):
            with tr.span("outer", f"outer-{i}"):
                time.sleep(0.002)
                with tr.span("inner", f"inner-{i}"):
                    time.sleep(0.002)
                time.sleep(0.002)

        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = tr.spans()
        assert len(spans) == 8
        by_tid = {}
        for r in spans:
            by_tid.setdefault(r.tid, {})[r.stage] = r
        assert len(by_tid) == 4
        for tid, pair in by_tid.items():
            outer, inner = pair["outer"], pair["inner"]
            # proper containment: inner starts after and ends before outer
            assert outer.t0 <= inner.t0
            assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9
            assert outer.name.split("-")[1] == inner.name.split("-")[1]

    def test_reset_clears_all_threads(self):
        tr = Tracer()
        with tr.span("a"):
            pass
        t = threading.Thread(target=lambda: tr.span("b").__enter__().__exit__(None, None, None))
        t.start()
        t.join()
        assert len(tr.spans()) == 2
        tr.reset()
        assert tr.spans() == []
        assert tr.counters() == []

    def test_counters(self):
        tr = Tracer()
        tr.count("depth", 1)
        tr.count("depth", 3)
        vals = [c.value for c in tr.counters()]
        assert vals == [1.0, 3.0]

    def test_module_level_span_routes_to_global(self):
        tr = trace.start()
        with trace.span("x", "y", 7):
            pass
        trace.count("c", 2)
        trace.stop()
        assert len(tr.spans()) == 1
        assert tr.spans()[0].nbytes == 7
        assert len(tr.counters()) == 1
        # after stop() the hot path is null again
        assert trace.span("x") is NULL_SPAN


class TestDisabledFastPath:
    def test_null_singleton(self):
        assert trace.get_tracer() is None
        assert trace.span("storage_read", "p") is NULL_SPAN
        # disabled tracer (installed but off) also short-circuits
        t = Tracer(enabled=False)
        trace.set_tracer(t)
        assert trace.span("storage_read", "p") is NULL_SPAN
        assert t.span("storage_read") is NULL_SPAN
        assert t.spans() == []

    def test_no_allocations_per_op_when_disabled(self):
        """The disabled path must not allocate: 10k span enters/exits leave
        no per-op garbage behind (shared singleton, no kwargs)."""
        def burn(n):
            for _ in range(n):
                with trace.span("storage_read", "path"):
                    pass
                trace.count("gauge", 1.0)
                trace.instant("storage_read", "path", 10)

        burn(100)  # warm up interned ints etc.
        tracemalloc.start()
        burn(10_000)
        _current, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        # a per-op allocation of even one 56-byte object would show ~560 KB
        assert peak < 16_384, f"disabled tracing allocated {peak} bytes"


# ---------------------------------------------------------------------------
# percentiles / aggregation / overlap
# ---------------------------------------------------------------------------
class TestPercentile:
    def test_empty_series(self):
        assert trace.percentile([], 50) == 0.0
        assert trace.percentile([], 99) == 0.0

    def test_singleton_series(self):
        for q in (0, 50, 95, 99, 100):
            assert trace.percentile([4.5], q) == 4.5

    def test_interpolation(self):
        xs = [0.0, 10.0]
        assert trace.percentile(xs, 50) == 5.0
        assert trace.percentile(list(range(101)), 95) == 95.0

    def test_bad_q(self):
        with pytest.raises(ValueError):
            trace.percentile([1.0], 101)
        with pytest.raises(ValueError):
            trace.percentile([1.0], -1)

    def test_unsorted_input(self):
        assert trace.percentile([9.0, 1.0, 5.0], 50) == 5.0


class TestAggregate:
    def test_per_stage_rollup(self):
        spans = [
            mkspan("read", 0.0, 0.010, nbytes=100),
            mkspan("read", 0.1, 0.030, nbytes=300),
            mkspan("write", 0.2, 0.050, nbytes=1000),
        ]
        stats = trace.aggregate(spans)
        assert stats["read"].ops == 2
        assert stats["read"].bytes == 400
        assert stats["read"].p50_ms == pytest.approx(20.0)
        assert stats["write"].ops == 1
        assert stats["write"].p99_ms == pytest.approx(50.0)
        # sorted by descending total time
        assert list(stats) == ["write", "read"]

    def test_empty(self):
        assert trace.aggregate([]) == {}


class TestOverlap:
    def test_partial_overlap(self):
        spans = [
            mkspan("compute", 0.0, 1.0, tid=1),
            mkspan("decode", 0.2, 0.3, tid=2),
            mkspan("prefetch", 0.6, 0.2, tid=2),
        ]
        ov = trace.overlap_ratio(spans)
        assert ov == pytest.approx(0.5)  # 0.3 + 0.2 of 1.0s compute

    def test_no_compute(self):
        assert trace.overlap_ratio([mkspan("decode", 0, 1)]) == 0.0

    def test_disjoint(self):
        spans = [
            mkspan("compute", 0.0, 1.0),
            mkspan("decode", 2.0, 1.0),
        ]
        assert trace.overlap_ratio(spans) == 0.0

    def test_union_merges_concurrent_bg(self):
        # two overlapping decodes on different threads must not double count
        spans = [
            mkspan("compute", 0.0, 1.0, tid=1),
            mkspan("decode", 0.0, 0.6, tid=2),
            mkspan("decode", 0.3, 0.4, tid=3),
        ]
        assert trace.overlap_ratio(spans) == pytest.approx(0.7)

    def test_storage_read_not_in_default_bg(self):
        """Checkpoint/drain reads must not masquerade as input-pipeline
        activity: a bare storage_read overlapping compute contributes 0."""
        spans = [
            mkspan("compute", 0.0, 1.0, tid=1),
            mkspan("storage_read", 0.0, 1.0, tid=2),  # e.g. a drain read
        ]
        assert trace.overlap_ratio(spans) == 0.0
        # but explicit bg selection still works
        assert trace.overlap_ratio(
            spans, bg_stages=("storage_read",)) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------
class TestChromeExport:
    def test_schema(self):
        tr = Tracer()
        with tr.span("storage_read", "f.bin") as sp:
            sp.set_bytes(64)
        tr.count("depth", 2)
        obj = trace.to_chrome_trace(tr.spans(), tr.counters(),
                                    process_name="p")
        assert set(obj) == {"traceEvents", "displayTimeUnit"}
        phases = {e["ph"] for e in obj["traceEvents"]}
        assert {"M", "X", "C"} <= phases
        x = next(e for e in obj["traceEvents"] if e["ph"] == "X")
        assert x["cat"] == "storage_read"
        assert x["name"] == "f.bin"
        assert x["args"]["bytes"] == 64
        assert x["ts"] >= 0 and x["dur"] >= 0  # microseconds
        json.dumps(obj)  # must be serializable

    def test_round_trip(self):
        spans = [
            mkspan("storage_read", 0.5, 0.25, tid=11, nbytes=4096, name="a"),
            mkspan("decode", 0.75, 0.1, tid=12, nbytes=0, name="load"),
            SpanRecord(stage="compute", name="step", tid=11, thread="t11",
                       t0=1.0, dur=0.5, nbytes=0, args={"step": 3}),
        ]
        counters = [trace.CounterRecord("depth", 0.6, 2.0, 11)]
        blob = json.dumps(trace.to_chrome_trace(spans, counters))
        back_spans, back_counters = trace.from_chrome_trace(blob)
        assert len(back_spans) == len(spans)
        for a, b in zip(sorted(spans, key=lambda r: r.t0), back_spans):
            assert b.stage == a.stage
            assert b.name == a.name
            assert b.tid == a.tid
            assert b.thread == a.thread
            assert b.t0 == pytest.approx(a.t0)
            assert b.dur == pytest.approx(a.dur)
            assert b.nbytes == a.nbytes
        assert back_spans[-1].args == {"step": 3}
        (c,) = back_counters
        assert (c.name, c.value) == ("depth", 2.0)
        assert c.t == pytest.approx(0.6)

    def test_dump_to_file(self, tmp_path):
        tr = Tracer()
        with tr.span("storage_write", "x"):
            pass
        path = tmp_path / "trace.json"
        trace.dump_chrome_trace(tr, str(path))
        loaded_spans, _ = trace.from_chrome_trace(path.read_text())
        assert loaded_spans[0].stage == "storage_write"


# ---------------------------------------------------------------------------
# markdown report
# ---------------------------------------------------------------------------
class TestMarkdown:
    def test_empty(self):
        md = trace.to_markdown([])
        assert "no spans" in md

    def test_stages_and_overlap_present(self):
        spans = [
            mkspan("compute", 0.0, 1.0, tid=1),
            mkspan("storage_read", 0.2, 0.5, tid=2, nbytes=2_000_000),
        ]
        md = trace.to_markdown(spans, title="T")
        assert "# T" in md
        assert "storage_read" in md
        assert "overlap ratio" in md
        assert "2.00" in md  # MB column


# ---------------------------------------------------------------------------
# end-to-end: instrumented core layers
# ---------------------------------------------------------------------------
class TestInstrumentation:
    def test_storage_pipeline_checkpoint_spans(self, tmp_storage):
        import numpy as np

        from repro.core import Dataset
        from repro.core.checkpoint import CheckpointSaver

        tr = trace.start()
        try:
            tmp_storage.write_file("a.bin", b"z" * 2048)
            loaded = (
                Dataset.from_tensor_slices(["a.bin"])
                .map(tmp_storage.read_file, num_parallel_calls=2)
                .prefetch(1)
                .as_numpy()
            )
            assert len(loaded[0]) == 2048
            saver = CheckpointSaver(tmp_storage, "ckpt/m", sync=False)
            saver.save(1, {"w": np.zeros(8, np.float32)})
            saver.restore_pytree({"w": np.zeros(8, np.float32)})
        finally:
            trace.stop()
        stages = {r.stage for r in tr.spans()}
        assert trace.STAGE_STORAGE_READ in stages
        assert trace.STAGE_STORAGE_WRITE in stages
        assert trace.STAGE_DECODE in stages
        assert trace.STAGE_PREFETCH in stages
        assert trace.STAGE_CKPT_WRITE in stages
        assert trace.STAGE_CKPT_SERIALIZE in stages
        assert trace.STAGE_CKPT_RESTORE in stages
        # read bytes attributed
        reads = [r for r in tr.spans() if r.stage == trace.STAGE_STORAGE_READ]
        assert any(r.nbytes == 2048 for r in reads)
        # prefetch buffer gauge sampled
        assert any(c.name == "prefetch_buffer" for c in tr.counters())

    def test_burst_buffer_drain_span(self, fast_slow_storage):
        import numpy as np

        from repro.core.engine import CheckpointEngine

        fast, slow = fast_slow_storage
        tr = trace.start()
        try:
            bb = CheckpointEngine((fast, slow), "ckpt/m", sync=False)
            bb.save(1, {"w": np.ones(256, np.float32)})
            bb.wait()
            bb.close()
        finally:
            trace.stop()
        drains = [r for r in tr.spans() if r.stage == trace.STAGE_DRAIN]
        assert len(drains) == 1
        assert drains[0].nbytes > 0
        assert "drain:ckpt/m-1" in drains[0].name

    @pytest.mark.parametrize("engine", ["direct", "async", "bb", "asyncbb"])
    def test_one_serialize_span_inside_each_save(self, engine, tmp_path):
        """Every engine serializes once per save, inside the ``save:`` span
        of the same thread, and the span carries the shard bytes packed."""
        import numpy as np

        from repro.core.recovery import CheckpointManager
        from repro.core.storage import NativeStorage

        slow = NativeStorage(str(tmp_path / "slow"))
        fast = NativeStorage(str(tmp_path / "fast"))
        state = {"w": np.ones((64, 8), np.float32), "b": np.arange(8),
                 "s": np.float32(2)}
        tr = trace.start()
        try:
            mgr = CheckpointManager(slow, "ckpt/m", engine=engine,
                                    fast_storage=fast, n_shards=3,
                                    sync=False)
            mgr.save(1, state)
            mgr.wait()
            mgr.close()
        finally:
            trace.stop()
        spans = tr.spans()
        ser = [r for r in spans if r.stage == trace.STAGE_CKPT_SERIALIZE]
        saves = [r for r in spans if r.stage == trace.STAGE_CKPT_WRITE]
        assert len(ser) == 1 and len(saves) == 1
        (sv,), (sp,) = saves, ser
        assert sv.name.startswith("save:") and sp.tid == sv.tid
        assert sv.t0 <= sp.t0 and sp.t0 + sp.dur <= sv.t0 + sv.dur + 1e-9
        shard_bytes = sum(len(slow.read_file(f"ckpt/m-1.data-{i:05d}-of-00003"))
                          for i in range(3))
        assert sp.nbytes == shard_bytes == sum(
            np.asarray(v).nbytes for v in state.values())

    def test_untraced_by_default(self, tmp_storage):
        tmp_storage.write_file("b.bin", b"q")
        tmp_storage.read_file("b.bin")  # no global tracer: must not raise
        assert trace.get_tracer() is None
