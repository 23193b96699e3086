"""Storage tiers for the I/O benchmarks and the burst buffer.

The paper (Table I) benchmarks four devices with IOR:

    ============  ==========  ===========
    device        max read    max write
    ============  ==========  ===========
    HDD           163.00 MB/s  133.14 MB/s
    SSD           280.55 MB/s  195.05 MB/s
    Intel Optane  1603.06 MB/s 511.78 MB/s
    Lustre        1968.62 MB/s 991.91 MB/s
    ============  ==========  ===========

This container has a single disk (and a single core), so we reproduce the
paper's *environment* with a calibrated token-bucket simulator:
:class:`SimulatedStorage` performs real file I/O against a backing directory
but paces it so that aggregate and per-stream bandwidth, seek latency, and
seek contention match the device model.  :class:`NativeStorage` is the
passthrough used on real machines.

Every storage object exposes the same tiny interface the rest of the
framework uses (read_file/write_file/fsync_dir/listdir/...), mirroring how
TensorFlow's file-system adapters (POSIX/S3/GCS/HDFS — paper Fig. 1) share
one interface.
"""
from __future__ import annotations

import os
import shutil
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

from .. import metrics, trace
from .stats import IOTracer


def _op_metrics(op: str, tier: str, nbytes: int, dur_s: float) -> None:
    """Per-tier op/bytes counters + latency sketch (one enabled() check at
    each call site keeps the disabled path allocation-free)."""
    metrics.inc(f"storage.{op}_ops", 1, tier=tier)
    metrics.inc(f"storage.{op}_bytes", nbytes, tier=tier)
    metrics.observe(f"storage.{op}_s", dur_s, tier=tier)


# ---------------------------------------------------------------------------
# Device models
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TierSpec:
    """Bandwidth/latency model of one storage device.

    ``seek_contention`` inflates per-op latency as concurrency grows
    (``lat_n = seek_latency * (1 + seek_contention * (n_inflight - 1))``) —
    this is what makes HDD thread-scaling saturate around the paper's 2.3x
    while Lustre keeps scaling to ~7.8x.
    """

    name: str
    read_bw: float          # aggregate B/s
    write_bw: float         # aggregate B/s
    stream_read_bw: float   # single-stream B/s
    stream_write_bw: float  # single-stream B/s
    seek_latency: float     # s per op
    seek_contention: float  # dimensionless


# Calibrated against paper Table I (aggregate) + Fig. 4/5 (scaling shape).
TIERS: Dict[str, TierSpec] = {
    "hdd": TierSpec("hdd", 163.00e6, 133.14e6, 75e6, 70e6, 8e-3, 0.42),
    "ssd": TierSpec("ssd", 280.55e6, 195.05e6, 150e6, 110e6, 0.1e-3, 0.05),
    "optane": TierSpec("optane", 1603.06e6, 511.78e6, 900e6, 300e6, 0.01e-3, 0.02),
    "lustre": TierSpec("lustre", 1968.62e6, 991.91e6, 260e6, 135e6, 0.5e-3, 0.0),
}


class Storage:
    """Abstract file-store interface (the TF file-system-adapter analogue)."""

    name = "abstract"

    # -- reads -------------------------------------------------------------
    def read_file(self, path: str) -> bytes:
        raise NotImplementedError

    def read_range(self, path: str, offset: int, length: int) -> bytes:
        """Read ``length`` bytes starting at ``offset`` (streaming reads).

        Default implementation slices a full read — subclasses override to
        avoid materializing the whole file.
        """
        return self.read_file(path)[offset : offset + length]

    # -- writes ------------------------------------------------------------
    def write_file(self, path: str, data: bytes, sync: bool = False) -> None:
        raise NotImplementedError

    def append_file(self, path: str, data: bytes, sync: bool = False) -> None:
        """Append ``data`` to ``path`` (streaming writes; pays write cost)."""
        raise NotImplementedError

    def write_range(self, path: str, offset: int, data: bytes,
                    sync: bool = False) -> None:
        """pwrite-style positional write: place ``data`` at ``offset``.

        Writes past EOF extend the file (the gap reads as zeros), so
        concurrent writers can land disjoint ranges of one file in any
        order — this is what lets a single large checkpoint shard drain on
        multiple streams instead of one serial ``copy_to`` chain.

        The default is a read-modify-write over the whole file (correct for
        any backend, O(file) per call); :class:`NativeStorage` and
        :class:`SimulatedStorage` override with a real ``os.pwrite``.
        """
        existing = self.read_file(path) if self.exists(path) else b""
        if len(existing) < offset:
            existing += b"\x00" * (offset - len(existing))
        new = existing[:offset] + bytes(data) + existing[offset + len(data):]
        self.write_file(path, new, sync=sync)

    def fsync_dir(self, path: str) -> None:
        """paper §III-C: syncfs() after Saver returns."""
        raise NotImplementedError

    # -- namespace ---------------------------------------------------------
    def listdir(self, path: str) -> List[str]:
        raise NotImplementedError

    def exists(self, path: str) -> bool:
        raise NotImplementedError

    def makedirs(self, path: str) -> None:
        raise NotImplementedError

    def remove(self, path: str) -> None:
        raise NotImplementedError

    def rename(self, src: str, dst: str) -> None:
        raise NotImplementedError

    def size(self, path: str) -> int:
        raise NotImplementedError

    def drop_caches(self) -> None:
        """posix_fadvise(DONTNEED) analogue (paper §IV)."""

    def copy_to(self, src_path: str, dst_storage: "Storage", dst_path: str,
                chunk: int = 8 << 20) -> None:
        """Tier-to-tier copy that pays read cost here and write cost there
        (used by the burst-buffer drainer).

        Streams ``chunk`` bytes at a time through :meth:`read_range` /
        :meth:`append_file`, so peak memory is one chunk — a multi-GB
        checkpoint shard never materializes as a single blob.
        """
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        size = self.size(src_path)
        if size <= chunk:
            dst_storage.write_file(dst_path, self.read_file(src_path),
                                   sync=False)
            return
        offset = 0
        while offset < size:
            data = self.read_range(src_path, offset, min(chunk, size - offset))
            if offset == 0:
                dst_storage.write_file(dst_path, data, sync=False)
            else:
                dst_storage.append_file(dst_path, data, sync=False)
            offset += len(data)


class NativeStorage(Storage):
    """Direct POSIX passthrough rooted at ``root``."""

    name = "native"

    def __init__(self, root: str, tracer: Optional[IOTracer] = None):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self.tracer = tracer

    def _abs(self, path: str) -> str:
        return os.path.join(self.root, path)

    def read_file(self, path: str) -> bytes:
        m = metrics.enabled()
        t0 = time.monotonic() if m else 0.0
        with trace.span(trace.STAGE_STORAGE_READ, path) as sp:
            with open(self._abs(path), "rb") as f:
                data = f.read()
            sp.set_bytes(len(data))
        if m:
            _op_metrics("read", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("read", len(data))
        return data

    def read_range(self, path: str, offset: int, length: int) -> bytes:
        m = metrics.enabled()
        t0 = time.monotonic() if m else 0.0
        with trace.span(trace.STAGE_STORAGE_READ, path) as sp:
            with open(self._abs(path), "rb") as f:
                f.seek(offset)
                data = f.read(length)
            sp.set_bytes(len(data))
        if m:
            _op_metrics("read", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("read", len(data))
        return data

    def write_file(self, path: str, data: bytes, sync: bool = False) -> None:
        m = metrics.enabled()
        t0 = time.monotonic() if m else 0.0
        with trace.span(trace.STAGE_STORAGE_WRITE, path, len(data)):
            ap = self._abs(path)
            os.makedirs(os.path.dirname(ap) or ".", exist_ok=True)
            with open(ap, "wb") as f:
                f.write(data)
                if sync:
                    f.flush()
                    os.fsync(f.fileno())
        if m:
            _op_metrics("write", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("write", len(data))

    def append_file(self, path: str, data: bytes, sync: bool = False) -> None:
        m = metrics.enabled()
        t0 = time.monotonic() if m else 0.0
        with trace.span(trace.STAGE_STORAGE_WRITE, path, len(data)):
            ap = self._abs(path)
            os.makedirs(os.path.dirname(ap) or ".", exist_ok=True)
            with open(ap, "ab") as f:
                f.write(data)
                if sync:
                    f.flush()
                    os.fsync(f.fileno())
        if m:
            _op_metrics("write", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("write", len(data))

    def write_range(self, path: str, offset: int, data: bytes,
                    sync: bool = False) -> None:
        m = metrics.enabled()
        t0 = time.monotonic() if m else 0.0
        with trace.span(trace.STAGE_STORAGE_WRITE, path, len(data)):
            ap = self._abs(path)
            os.makedirs(os.path.dirname(ap) or ".", exist_ok=True)
            fd = os.open(ap, os.O_WRONLY | os.O_CREAT, 0o644)
            try:
                os.pwrite(fd, bytes(data), offset)
                if sync:
                    os.fsync(fd)
            finally:
                os.close(fd)
        if m:
            _op_metrics("write", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("write", len(data))

    def fsync_dir(self, path: str) -> None:
        ap = self._abs(path)
        try:
            fd = os.open(ap, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        except OSError:
            pass

    def listdir(self, path: str) -> List[str]:
        return sorted(os.listdir(self._abs(path)))

    def exists(self, path: str) -> bool:
        return os.path.exists(self._abs(path))

    def makedirs(self, path: str) -> None:
        os.makedirs(self._abs(path), exist_ok=True)

    def remove(self, path: str) -> None:
        ap = self._abs(path)
        if os.path.isdir(ap):
            shutil.rmtree(ap)
        elif os.path.exists(ap):
            os.remove(ap)

    def rename(self, src: str, dst: str) -> None:
        os.replace(self._abs(src), self._abs(dst))

    def size(self, path: str) -> int:
        return os.path.getsize(self._abs(path))

    def drop_caches(self) -> None:
        # Advise the kernel we no longer need the pages of files under root.
        if not hasattr(os, "posix_fadvise"):
            return
        for dirpath, _dirs, files in os.walk(self.root):
            for fn in files:
                try:
                    fd = os.open(os.path.join(dirpath, fn), os.O_RDONLY)
                    try:
                        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                    finally:
                        os.close(fd)
                except OSError:
                    pass


class _TokenBucket:
    """Pacing primitive: admission at ``rate`` B/s, shared by all streams.

    Instead of sleeping inside a lock, each acquire reserves a time slot
    [start, start+bytes/rate) on a virtual device timeline and sleeps until
    its slot ends — giving FIFO bandwidth sharing that behaves like a device
    queue under concurrency.
    """

    def __init__(self, rate: float):
        self.rate = float(rate)
        self._lock = threading.Lock()
        self._next_free = 0.0  # virtual device-free time (monotonic)

    def reserve(self, nbytes: int) -> float:
        """Reserve a slot; returns the monotonic time the device would
        finish this transfer (caller sleeps until then)."""
        now = time.monotonic()
        if self.rate <= 0 or nbytes <= 0:
            return now
        dur = nbytes / self.rate
        with self._lock:
            start = max(now, self._next_free)
            end = start + dur
            self._next_free = end
        return end

    def acquire(self, nbytes: int) -> None:
        end = self.reserve(nbytes)
        delay = end - time.monotonic()
        if delay > 0:
            time.sleep(delay)


class SimulatedStorage(Storage):
    """Real files under ``root``, paced to behave like ``spec``.

    Reads/writes really hit the backing filesystem (so correctness is real),
    then sleep whatever extra time the modelled device would have needed.
    A per-op seek latency with a concurrency-dependent contention factor plus
    per-stream and aggregate token buckets reproduce the thread-scaling
    behaviour of the paper's four devices.
    """

    def __init__(self, root: str, spec: TierSpec,
                 tracer: Optional[IOTracer] = None,
                 time_scale: float = 1.0):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self.spec = spec
        self.name = spec.name
        self.tracer = tracer
        # time_scale < 1 speeds up the simulation uniformly (all bandwidths
        # multiplied by 1/time_scale) so benchmarks finish quickly while
        # preserving every *ratio* the paper reports.
        self.time_scale = float(time_scale)
        self._read_bucket = _TokenBucket(spec.read_bw / self.time_scale)
        self._write_bucket = _TokenBucket(spec.write_bw / self.time_scale)
        self._lock = threading.Lock()
        self._inflight = 0

    # -- concurrency tracking ------------------------------------------------
    def _enter(self) -> int:
        with self._lock:
            self._inflight += 1
            return self._inflight

    def _exit(self) -> None:
        with self._lock:
            self._inflight -= 1

    def _seek_latency(self, n_inflight: int) -> float:
        lat = self.spec.seek_latency * (
            1.0 + self.spec.seek_contention * max(0, n_inflight - 1)
        )
        return lat * self.time_scale

    def _seek(self, n_inflight: int) -> None:
        lat = self._seek_latency(n_inflight)
        if lat > 0:
            time.sleep(lat)

    def paced_sleep(self, seconds: float) -> None:
        """Sleep ``seconds`` of *modelled* time, i.e. ``seconds *
        time_scale`` of wall clock.  Inject as ``RetryPolicy(sleep=...)`` so
        retry backoff runs on the same scaled clock as the device pacing —
        the faulty-path latency tax then reproduces at any ``time_scale``."""
        wall = seconds * self.time_scale
        if wall > 0:
            time.sleep(wall)

    def _abs(self, path: str) -> str:
        return os.path.join(self.root, path)

    def _pace(self, t0: float, n_inflight: int, nbytes: int,
              stream_bw: float, bucket: _TokenBucket) -> None:
        """Sleep until the modelled device would have finished the op: the
        later of single-stream time (incl. seek) and the shared device-queue
        slot — real backing-I/O time is credited, so fast tiers aren't
        penalized by the real disk."""
        stream_end = t0 + self._seek_latency(n_inflight) + nbytes / (
            stream_bw / self.time_scale)
        bucket_end = bucket.reserve(nbytes)
        delay = max(stream_end, bucket_end) - time.monotonic()
        if delay > 0:
            time.sleep(delay)

    # -- I/O -----------------------------------------------------------------
    def read_file(self, path: str) -> bytes:
        n = self._enter()
        t0 = time.monotonic()
        # span covers the modelled device time (pacing sleeps included):
        # the trace shows what the simulated tier would really cost
        with trace.span(trace.STAGE_STORAGE_READ, path) as sp:
            try:
                with open(self._abs(path), "rb") as f:
                    data = f.read()
                sp.set_bytes(len(data))
                self._pace(t0, n, len(data), self.spec.stream_read_bw,
                           self._read_bucket)
            finally:
                self._exit()
        # metric latency covers the modelled device time (pacing included)
        if metrics.enabled():
            _op_metrics("read", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("read", len(data))
        return data

    def read_range(self, path: str, offset: int, length: int) -> bytes:
        n = self._enter()
        t0 = time.monotonic()
        with trace.span(trace.STAGE_STORAGE_READ, path) as sp:
            try:
                with open(self._abs(path), "rb") as f:
                    f.seek(offset)
                    data = f.read(length)
                sp.set_bytes(len(data))
                self._pace(t0, n, len(data), self.spec.stream_read_bw,
                           self._read_bucket)
            finally:
                self._exit()
        if metrics.enabled():
            _op_metrics("read", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("read", len(data))
        return data

    def write_file(self, path: str, data: bytes, sync: bool = False) -> None:
        n = self._enter()
        t0 = time.monotonic()
        with trace.span(trace.STAGE_STORAGE_WRITE, path, len(data)):
            try:
                ap = self._abs(path)
                os.makedirs(os.path.dirname(ap) or ".", exist_ok=True)
                with open(ap, "wb") as f:
                    f.write(data)
                    # NOTE: no real fsync — durability cost is part of the
                    # *modelled* device time; paying the backing disk's real
                    # fsync would distort every tier with a constant unrelated
                    # to the modelled device.
                self._pace(t0, n, len(data), self.spec.stream_write_bw,
                           self._write_bucket)
            finally:
                self._exit()
        if metrics.enabled():
            _op_metrics("write", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("write", len(data))

    def append_file(self, path: str, data: bytes, sync: bool = False) -> None:
        n = self._enter()
        t0 = time.monotonic()
        with trace.span(trace.STAGE_STORAGE_WRITE, path, len(data)):
            try:
                ap = self._abs(path)
                os.makedirs(os.path.dirname(ap) or ".", exist_ok=True)
                with open(ap, "ab") as f:
                    f.write(data)
                self._pace(t0, n, len(data), self.spec.stream_write_bw,
                           self._write_bucket)
            finally:
                self._exit()
        if metrics.enabled():
            _op_metrics("write", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("write", len(data))

    def write_range(self, path: str, offset: int, data: bytes,
                    sync: bool = False) -> None:
        n = self._enter()
        t0 = time.monotonic()
        with trace.span(trace.STAGE_STORAGE_WRITE, path, len(data)):
            try:
                ap = self._abs(path)
                os.makedirs(os.path.dirname(ap) or ".", exist_ok=True)
                fd = os.open(ap, os.O_WRONLY | os.O_CREAT, 0o644)
                try:
                    os.pwrite(fd, bytes(data), offset)
                finally:
                    os.close(fd)
                self._pace(t0, n, len(data), self.spec.stream_write_bw,
                           self._write_bucket)
            finally:
                self._exit()
        if metrics.enabled():
            _op_metrics("write", self.name, len(data), time.monotonic() - t0)
        if self.tracer:
            self.tracer.record("write", len(data))

    def fsync_dir(self, path: str) -> None:
        # Modelled as one seek-class operation.
        n = self._enter()
        try:
            self._seek(n)
        finally:
            self._exit()

    # -- namespace (unthrottled metadata ops) --------------------------------
    def listdir(self, path: str) -> List[str]:
        return sorted(os.listdir(self._abs(path)))

    def exists(self, path: str) -> bool:
        return os.path.exists(self._abs(path))

    def makedirs(self, path: str) -> None:
        os.makedirs(self._abs(path), exist_ok=True)

    def remove(self, path: str) -> None:
        ap = self._abs(path)
        if os.path.isdir(ap):
            shutil.rmtree(ap)
        elif os.path.exists(ap):
            os.remove(ap)

    def rename(self, src: str, dst: str) -> None:
        os.replace(self._abs(src), self._abs(dst))

    def size(self, path: str) -> int:
        return os.path.getsize(self._abs(path))


def make_storage(kind: str, root: str, tracer: Optional[IOTracer] = None,
                 time_scale: float = 1.0) -> Storage:
    """Factory: ``kind`` is 'native' or one of TIERS (hdd/ssd/optane/lustre)."""
    if kind == "native":
        return NativeStorage(root, tracer)
    if kind in TIERS:
        return SimulatedStorage(root, TIERS[kind], tracer, time_scale)
    raise ValueError(f"unknown storage kind {kind!r}; options: native, {list(TIERS)}")
