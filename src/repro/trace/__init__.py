"""repro.trace — fine-grained I/O tracing & telemetry (tf-Darshan analogue).

The source paper characterizes DL I/O with coarse 1 Hz dstat counters
(§IV-B, Figs. 8/10); its follow-up, *tf-Darshan* (arXiv:2008.04395), shows
that per-operation spans attributed to pipeline stages are what actually
explain ingestion and checkpoint behaviour.  This package is that
follow-up's instrumentation layer for this codebase — the telemetry spine
every subsystem reports through.

Subsystem map:

* :mod:`repro.trace.tracer` — the collector.  :class:`Tracer` keeps
  per-thread span/counter buffers (lock only on first touch per thread);
  module-level :func:`span` / :func:`instant` / :func:`count` are the
  hot-path hooks used by ``repro.core`` and cost one global check plus a
  shared no-op singleton when tracing is off.  Stage constants
  (``STAGE_STORAGE_READ``, ``STAGE_DECODE``, ``STAGE_PREFETCH``,
  ``STAGE_CKPT_WRITE``, ``STAGE_DRAIN``, ``STAGE_COMPUTE``, ...) form the
  attribution taxonomy.
* :mod:`repro.trace.report` — Darshan-style reduction: per-stage op
  counts, bytes, latency percentiles (:func:`aggregate`,
  :func:`percentile`), the compute/input-pipeline :func:`overlap_ratio`
  (paper Fig. 6 made measurable), and :func:`to_markdown`.
* :mod:`repro.trace.export` — Chrome ``trace_event`` JSON for
  Perfetto/chrome://tracing (:func:`to_chrome_trace`,
  :func:`dump_chrome_trace`) plus the inverse :func:`from_chrome_trace`
  for lossless round-trips.

Instrumented producers: ``core/storage.py`` (reads/writes, incl. simulated
device pacing), ``core/dataset.py`` (per-element map/decode),
``core/prefetcher.py`` (background fetches + buffer-depth counter),
``core/checkpoint.py`` (save/restore), ``core/engine.py`` (snapshot, stage, drains),
``train/trainer.py`` (per-step data-wait vs compute, and inside the step
its dispatch and syncs; the save, the stop and the close),
``core/recovery.py`` (validation, restore, iterator seek on resume) and
the garbage collector while :func:`start` is in force.  ``core.stats.
IOTracer`` keeps the dstat-style per-interval byte buckets beside it.

Typical use::

    from repro import trace

    tracer = trace.start()               # install global collector
    ...run pipeline / training...
    trace.dump_chrome_trace(tracer, "trace.json")   # open in Perfetto
    print(trace.to_markdown(tracer.spans(), counters=tracer.counters()))
    trace.stop()
"""
from .tracer import (
    INPUT_PIPELINE_STAGES,
    NULL_SPAN,
    STAGE_CACHE,
    STAGE_CKPT_BACKPRESSURE,
    STAGE_CKPT_CLOSE,
    STAGE_CKPT_RESTORE,
    STAGE_CKPT_SAVE,
    STAGE_CKPT_SERIALIZE,
    STAGE_CKPT_SNAPSHOT,
    STAGE_CKPT_VALIDATE,
    STAGE_CKPT_WRITE,
    STAGE_COMPUTE,
    STAGE_DATA_WAIT,
    STAGE_DECODE,
    STAGE_DEVICE_PREPROCESS,
    STAGE_DRAIN,
    STAGE_EPOCH_OPEN,
    STAGE_GC,
    STAGE_ITERATOR_SEEK,
    STAGE_PIPELINE_CLOSE,
    STAGE_PIPELINE_STATE,
    STAGE_PREEMPT,
    STAGE_PREEMPT_PROMOTE,
    STAGE_PREFETCH,
    STAGE_STAGE,
    STAGE_STEP_DISPATCH,
    STAGE_STEP_SYNC,
    STAGE_STORAGE_READ,
    STAGE_STORAGE_WRITE,
    CounterRecord,
    Span,
    SpanRecord,
    Tracer,
    count,
    enabled,
    get_tracer,
    instant,
    set_tracer,
    span,
    start,
    stop,
)
from .report import (
    StageStats,
    aggregate,
    busy_intervals,
    overlap_ratio,
    percentile,
    to_markdown,
)
from .export import dump_chrome_trace, from_chrome_trace, to_chrome_trace

__all__ = [
    # collector
    "Tracer", "Span", "SpanRecord", "CounterRecord", "NULL_SPAN",
    "span", "instant", "count", "start", "stop", "enabled",
    "get_tracer", "set_tracer",
    # stages
    "STAGE_STORAGE_READ", "STAGE_STORAGE_WRITE", "STAGE_DECODE",
    "STAGE_PREFETCH", "STAGE_CKPT_SNAPSHOT", "STAGE_CKPT_WRITE",
    "STAGE_CKPT_SERIALIZE", "STAGE_CKPT_RESTORE",
    "STAGE_DRAIN", "STAGE_STAGE", "STAGE_DATA_WAIT", "STAGE_COMPUTE",
    "STAGE_CACHE",
    "STAGE_STEP_DISPATCH", "STAGE_STEP_SYNC", "STAGE_CKPT_SAVE",
    "STAGE_PIPELINE_STATE", "STAGE_CKPT_BACKPRESSURE", "STAGE_PREEMPT",
    "STAGE_PREEMPT_PROMOTE", "STAGE_PIPELINE_CLOSE", "STAGE_CKPT_CLOSE",
    "STAGE_CKPT_VALIDATE", "STAGE_ITERATOR_SEEK", "STAGE_EPOCH_OPEN",
    "STAGE_DEVICE_PREPROCESS", "STAGE_GC",
    "INPUT_PIPELINE_STAGES",
    # reports
    "StageStats", "aggregate", "percentile", "overlap_ratio",
    "busy_intervals", "to_markdown",
    # export
    "to_chrome_trace", "dump_chrome_trace", "from_chrome_trace",
]
