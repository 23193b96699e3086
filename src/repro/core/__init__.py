"""repro.core — the paper's contribution: DL I/O as a first-class subsystem.

* :mod:`repro.core.dataset` — tf.data-like input pipeline (shuffle / shard /
  parallel map / interleave / fused map_and_batch / batch / prefetch /
  cache / ignore_errors), with closeable iterators end-to-end.
* :mod:`repro.core.readerpool` — the shared, lazily-sized reader thread
  pool every parallel pipeline stage schedules onto (grown once, reused
  across epochs and stages).
* :mod:`repro.core.prefetcher` — background-thread prefetcher + device
  double-buffering.
* :mod:`repro.core.records` — record container + image payloads + decode.
* :mod:`repro.core.storage` — storage tiers (native + Table-I-calibrated
  simulator: hdd / ssd / optane / lustre).
* :mod:`repro.core.checkpoint` — sharded TF-Saver-like checkpointing with
  parallel shard I/O (``io_threads``).
* :mod:`repro.core.engine` — :class:`CheckpointEngine`, the one save
  engine, set by its snapshot mode (``"sync"``: write on the caller's
  thread; ``"async"``: block for the host snapshot only) and its tiers
  (one, or a fast tier drained to a slow one on multiple streams — the
  paper's 2.6x burst buffer); ``save()`` returns a future-like
  :class:`SaveHandle`.
* :mod:`repro.core.faults` — :class:`FaultyStorage` fault injection
  (sticky failures, torn writes, reordered fsync + crash, and non-sticky
  transients), the crash-consistency proof harness for all of the above.
* :mod:`repro.core.retry` — :class:`RetryPolicy` (exponential backoff +
  full jitter + deadline, injectable ``sleep``) and the transparent
  :class:`RetryingStorage` wrapper that absorbs transient storage faults
  below every pipeline and checkpoint path.
* :mod:`repro.core.cache` — tiered block read-cache: :class:`BlockCache`
  (byte-budget LRU + single-flight dedup + optional fast-tier spill),
  the transparent :class:`CachingStorage` wrapper, and the
  :class:`ReadaheadScheduler` that prefetches upcoming shards' blocks
  ahead of the interleave cursor.
* :mod:`repro.core.recovery` — :class:`CheckpointManager`, the engine's
  four presets behind one checkpointer: retention
  (keep-last-k + keep-every-n), corruption-aware ``latest_valid()``
  restore, crash-safe GC, and TrainState-level ``resume()`` that also
  re-positions a :class:`~repro.core.dataset.ResumableIterator`.
* :mod:`repro.core.microbench` — STREAM-like ingestion benchmark.
* :mod:`repro.core.stats` — dstat-like I/O timeline view, an adapter over
  the :mod:`repro.trace` collector.

Telemetry: every I/O layer here (storage reads/writes, per-element
map/decode, prefetch fetches, checkpoint save/restore, burst-buffer
drains) emits stage-attributed spans through :mod:`repro.trace` — the
tf-Darshan-style subsystem.  Tracing is off by default; call
``repro.trace.start()`` to collect, then export with
``repro.trace.dump_chrome_trace`` (Perfetto) or summarize with
``repro.trace.to_markdown``.
"""
from .cache import BlockCache, CachingStorage, ReadaheadScheduler
from .dataset import (Dataset, ResumableIterator, ShardQuarantine,
                      image_pipeline, interleave_order,
                      sharded_image_pipeline, sharded_record_dataset)
from .prefetcher import PrefetchIterator, prefetch_to_device
from .readerpool import ReaderPool, reader_pool
from .storage import Storage, NativeStorage, SimulatedStorage, TIERS, make_storage
from .checkpoint import CheckpointSaver, PreemptionReport
from .engine import CheckpointEngine, DrainStallError, SaveHandle
from .faults import FaultInjected, FaultyStorage, TransientFault
from .retry import RetryPolicy, RetryingStorage
from .recovery import CheckpointManager, ResumeResult, latest_valid_step, \
    validate_step
from .stats import IOTracer, StepTimer

__all__ = [
    "Dataset", "ResumableIterator", "ShardQuarantine", "image_pipeline",
    "interleave_order", "sharded_image_pipeline", "sharded_record_dataset",
    "BlockCache", "CachingStorage", "ReadaheadScheduler",
    "PrefetchIterator", "prefetch_to_device", "ReaderPool", "reader_pool",
    "Storage", "NativeStorage", "SimulatedStorage", "TIERS", "make_storage",
    "CheckpointSaver", "PreemptionReport", "CheckpointEngine", "SaveHandle",
    "DrainStallError",
    "FaultInjected", "FaultyStorage", "TransientFault",
    "RetryPolicy", "RetryingStorage",
    "CheckpointManager", "ResumeResult", "latest_valid_step", "validate_step",
    "IOTracer", "StepTimer",
]
