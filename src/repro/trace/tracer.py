"""Span/event collector — the core of the tf-Darshan-style telemetry spine.

Design constraints (tf-Darshan, arXiv:2008.04395, §3: instrumentation must
not perturb the workload it observes):

* **Lock-cheap.** Each thread appends finished spans to its own buffer
  (created once per thread under a registry lock, then lock-free).  The
  only cross-thread synchronization on the hot path is the GIL-atomic
  ``list.append``.
* **Near-zero overhead when disabled.** The module-level :func:`span` /
  :func:`instant` / :func:`count` helpers check a single global and return a
  shared no-op singleton — no object allocation, no kwargs dict, nothing to
  garbage-collect.  Instrumented call sites therefore stay in hot paths
  permanently (storage reads, per-element decode) instead of being
  compiled out.
* **Thread-aware.** Every span records its OS thread id and thread name, so
  nesting is reconstructed per-thread (Chrome ``trace_event`` semantics:
  ``ph:"X"`` events nest by ts/dur containment within one tid).

Timestamps are seconds relative to the tracer's epoch (``time.monotonic``
at construction/reset), which keeps exported traces small and diff-able.
"""
from __future__ import annotations

import gc
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional


# ---------------------------------------------------------------------------
# Stage taxonomy (the attribution axis of every span)
# ---------------------------------------------------------------------------
STAGE_STORAGE_READ = "storage_read"       # Storage.read_file (incl. device pacing)
STAGE_STORAGE_WRITE = "storage_write"     # Storage.write_file
STAGE_DECODE = "decode"                   # Dataset.map fn (read+decode+resize)
STAGE_PREFETCH = "prefetch"               # background prefetch-thread fetch
STAGE_CKPT_SNAPSHOT = "checkpoint_snapshot"  # pytree -> host memory (blocking)
STAGE_CKPT_WRITE = "checkpoint_write"     # CheckpointSaver.save (serialize+write)
STAGE_CKPT_SERIALIZE = "checkpoint_serialize"  # tensors -> shard buffers
STAGE_CKPT_RESTORE = "checkpoint_restore" # CheckpointSaver.restore
STAGE_DRAIN = "bb_drain"                  # burst-buffer background drain
STAGE_STAGE = "bb_stage"                  # async-bb fast-tier staging write
#                                           (off the training thread)
STAGE_DATA_WAIT = "data_wait"             # trainer blocked on next(batch)
STAGE_COMPUTE = "compute"                 # trainer forward/backward/update
STAGE_CACHE = "cache"                     # block-cache miss fill / spill I/O
# Inside the training step, the save, the stop and the resume: each names
# what the training thread was doing while the device sat idle.
STAGE_STEP_DISPATCH = "step_dispatch"     # jitted train_step call to return
STAGE_STEP_SYNC = "step_sync"             # device_get of metrics / step counter
STAGE_CKPT_SAVE = "ckpt_save"             # Trainer's save, pipeline state incl.
STAGE_PIPELINE_STATE = "pipeline_state"   # iterator state() for the save meta
STAGE_CKPT_BACKPRESSURE = "ckpt_backpressure"  # async save waiting for a slot
STAGE_PREEMPT = "preempt"                 # final save and promote at a stop
STAGE_PREEMPT_PROMOTE = "preempt_promote"  # the checkpointer's preempt()
STAGE_PIPELINE_CLOSE = "pipeline_close"   # Trainer.close of the data iterator
STAGE_CKPT_CLOSE = "ckpt_close"           # CheckpointManager.close (joins)
STAGE_CKPT_VALIDATE = "ckpt_validate"     # finding the newest valid step
STAGE_ITERATOR_SEEK = "iterator_seek"     # restore_state of the data iterator
STAGE_EPOCH_OPEN = "epoch_open"           # next epoch's pipeline, first element
STAGE_DEVICE_PREPROCESS = "device_preprocess"  # batched resize+convert call
STAGE_GC = "gc"                           # a garbage collection (see start())

#: Stages that make up the input pipeline (vs. STAGE_COMPUTE) — the two
#: interval sets whose overlap is the paper's Fig. 6 observable.
#: STAGE_STORAGE_READ is deliberately absent: pipeline reads are already
#: nested inside STAGE_DECODE/STAGE_PREFETCH spans, while *non*-pipeline
#: reads (checkpoint restore, burst-buffer drain) would otherwise count as
#: "input pipeline busy" and inflate the overlap ratio.  STAGE_CACHE is
#: excluded for the same reason: cache fills nest inside the read path.
INPUT_PIPELINE_STAGES = (STAGE_DECODE, STAGE_PREFETCH, STAGE_DATA_WAIT)


# ---------------------------------------------------------------------------
# Records
# ---------------------------------------------------------------------------
@dataclass
class SpanRecord:
    """One completed span: ``[t0, t0+dur)`` seconds since the tracer epoch."""

    stage: str
    name: str
    tid: int
    thread: str
    t0: float
    dur: float
    nbytes: int = 0
    args: Optional[dict] = None


@dataclass
class CounterRecord:
    """Point sample of a named gauge (e.g. prefetch buffer depth)."""

    name: str
    t: float
    value: float
    tid: int


# ---------------------------------------------------------------------------
# Span handles
# ---------------------------------------------------------------------------
class _NullSpan:
    """Shared do-nothing span returned on the disabled path.

    A single module-level instance serves every disabled call site, so a
    ``with span(...)`` costs two method calls and zero allocations.
    """

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def set_bytes(self, nbytes: int) -> "_NullSpan":
        return self

    def set(self, **args) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class Span:
    """Live span handle; records itself into the tracer on ``__exit__``."""

    __slots__ = ("_tracer", "stage", "name", "_t0", "nbytes", "args")

    def __init__(self, tracer: "Tracer", stage: str, name: str, nbytes: int = 0):
        self._tracer = tracer
        self.stage = stage
        self.name = name
        self.nbytes = nbytes
        self.args = None

    def set_bytes(self, nbytes: int) -> "Span":
        self.nbytes = nbytes
        return self

    def set(self, **args) -> "Span":
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.monotonic()
        tr = self._tracer
        th = threading.current_thread()
        tr._append_span(
            SpanRecord(
                stage=self.stage,
                name=self.name,
                tid=th.ident or 0,
                thread=th.name,
                t0=self._t0 - tr._epoch,
                dur=t1 - self._t0,
                nbytes=self.nbytes,
                args=self.args,
            )
        )
        return False


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------
class _ThreadBuf:
    __slots__ = ("spans", "counters")

    def __init__(self):
        self.spans: List[SpanRecord] = []
        self.counters: List[CounterRecord] = []


class Tracer:
    """Thread-aware span/counter collector.

    Per-thread buffers are registered once (under ``_reg_lock``) and then
    appended to without any locking; snapshots (:meth:`spans`,
    :meth:`counters`) copy under the registry lock so concurrent recording
    stays safe.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._epoch = time.monotonic()
        self._local = threading.local()
        self._reg_lock = threading.Lock()
        self._bufs: List[_ThreadBuf] = []

    # -- recording ---------------------------------------------------------
    def _buf(self) -> _ThreadBuf:
        b = getattr(self._local, "buf", None)
        if b is None:
            b = _ThreadBuf()
            with self._reg_lock:
                self._bufs.append(b)
            self._local.buf = b
        return b

    def _append_span(self, rec: SpanRecord) -> None:
        self._buf().spans.append(rec)

    def span(self, stage: str, name: str = "", nbytes: int = 0):
        """Open a span; use as ``with tracer.span(stage, name) as sp:``."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, stage, name, nbytes)

    def instant(self, stage: str, name: str = "", nbytes: int = 0,
                t: Optional[float] = None) -> None:
        """Record a zero-duration event (e.g. a byte-counter sample)."""
        if not self.enabled:
            return
        th = threading.current_thread()
        if t is None:
            t = time.monotonic() - self._epoch
        self._append_span(
            SpanRecord(stage=stage, name=name, tid=th.ident or 0,
                       thread=th.name, t0=t, dur=0.0, nbytes=nbytes)
        )

    def count(self, name: str, value: float) -> None:
        """Sample a gauge (rendered as a counter track in Perfetto)."""
        if not self.enabled:
            return
        th = threading.current_thread()
        self._buf().counters.append(
            CounterRecord(name=name, t=time.monotonic() - self._epoch,
                          value=float(value), tid=th.ident or 0)
        )

    # -- snapshots ---------------------------------------------------------
    def spans(self) -> List[SpanRecord]:
        """Merged snapshot of all threads' spans, sorted by start time."""
        with self._reg_lock:
            out: List[SpanRecord] = []
            for b in self._bufs:
                out.extend(b.spans)
        out.sort(key=lambda r: (r.t0, -r.dur))
        return out

    def counters(self) -> List[CounterRecord]:
        with self._reg_lock:
            out: List[CounterRecord] = []
            for b in self._bufs:
                out.extend(b.counters)
        out.sort(key=lambda r: r.t)
        return out

    # -- lifecycle ---------------------------------------------------------
    def reset(self) -> None:
        with self._reg_lock:
            for b in self._bufs:
                b.spans.clear()
                b.counters.clear()
            self._epoch = time.monotonic()

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False


# ---------------------------------------------------------------------------
# Module-level API (what instrumented call sites use)
# ---------------------------------------------------------------------------
_active: Optional[Tracer] = None


def get_tracer() -> Optional[Tracer]:
    """The process-global tracer, or None when tracing is off."""
    return _active


_gc_hook: Optional["_GcHook"] = None


class _GcHook:
    """``gc.callbacks`` entry that records each collection as a
    ``STAGE_GC`` span on the thread that triggered it, with the
    generation in ``args``."""

    def __init__(self, tracer: Tracer):
        self.tracer = tracer
        self.t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t0 = time.monotonic()
            return
        tr = self.tracer
        if not tr.enabled:
            return
        th = threading.current_thread()
        tr._append_span(SpanRecord(
            stage=STAGE_GC, name="gc", tid=th.ident or 0, thread=th.name,
            t0=self.t0 - tr._epoch, dur=time.monotonic() - self.t0,
            args={"generation": info["generation"]}))


def _remove_gc_hook() -> None:
    global _gc_hook
    if _gc_hook is not None:
        gc.callbacks.remove(_gc_hook)
        _gc_hook = None


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` as the global one (None uninstalls), removing the
    garbage-collection hook of an earlier :func:`start`."""
    global _active
    _remove_gc_hook()
    _active = tracer
    return tracer


def start(enabled: bool = True) -> Tracer:
    """Install (and return) a fresh global tracer, and a ``gc.callbacks``
    hook that records every garbage collection as a ``STAGE_GC`` span
    until :func:`stop`."""
    global _gc_hook
    tracer = set_tracer(Tracer(enabled=enabled))
    _gc_hook = _GcHook(tracer)
    gc.callbacks.append(_gc_hook)
    return tracer


def stop() -> Optional[Tracer]:
    """Uninstall and return the global tracer (its records stay readable)
    and remove the garbage-collection hook."""
    global _active
    _remove_gc_hook()
    t, _active = _active, None
    return t


def enabled() -> bool:
    t = _active
    return t is not None and t.enabled


def span(stage: str, name: str = "", nbytes: int = 0):
    """Hot-path helper: a real span when tracing, the shared null span
    otherwise.  Call sites must pass positional args only so the disabled
    path allocates nothing."""
    t = _active
    if t is None or not t.enabled:
        return NULL_SPAN
    return Span(t, stage, name, nbytes)


def instant(stage: str, name: str = "", nbytes: int = 0) -> None:
    t = _active
    if t is not None and t.enabled:
        t.instant(stage, name, nbytes)


def count(name: str, value: float) -> None:
    t = _active
    if t is not None and t.enabled:
        t.count(name, value)
