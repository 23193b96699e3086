"""dstat-like I/O activity tracing (paper §IV-B, Fig. 8/10).

The paper traces disk activity with ``dstat`` at 1 Hz and plots MB read/written
per second.  :class:`IOTracer` reproduces that view: the per-interval
buckets are folded incrementally (bounded memory, like dstat itself).
Per-operation spans come from :mod:`repro.trace`, which the storage layer
reports to as well.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List


@dataclass
class _Bucket:
    read_bytes: int = 0
    write_bytes: int = 0
    read_ops: int = 0
    write_ops: int = 0


class IOTracer:
    """Thread-safe per-interval I/O byte counter (dstat analogue).

    Buckets are folded incrementally in ``record()`` so memory stays
    O(run length / interval), independent of op count.
    """

    def __init__(self, interval_s: float = 1.0):
        self.interval_s = float(interval_s)
        self._lock = threading.Lock()
        self._buckets: Dict[int, _Bucket] = {}
        self._t0 = time.monotonic()

    def reset(self) -> None:
        with self._lock:
            self._buckets.clear()
            self._t0 = time.monotonic()

    def record(self, kind: str, nbytes: int) -> None:
        if kind not in ("read", "write"):
            raise ValueError(
                f"unknown I/O kind {kind!r}; expected 'read' or 'write'"
            )
        t = time.monotonic() - self._t0
        idx = int(t / self.interval_s)
        with self._lock:
            b = self._buckets.setdefault(idx, _Bucket())
            if kind == "read":
                b.read_bytes += nbytes
                b.read_ops += 1
            else:
                b.write_bytes += nbytes
                b.write_ops += 1

    # -- reporting ---------------------------------------------------------
    def timeline(self) -> List[dict]:
        """Dense per-interval rows from t=0 to the last active interval."""
        with self._lock:
            if not self._buckets:
                return []
            last = max(self._buckets)
            rows = []
            for i in range(last + 1):
                b = self._buckets.get(i, _Bucket())
                rows.append(
                    dict(
                        t=i * self.interval_s,
                        read_mb=b.read_bytes / 1e6,
                        write_mb=b.write_bytes / 1e6,
                        read_ops=b.read_ops,
                        write_ops=b.write_ops,
                    )
                )
            return rows

    def totals(self) -> dict:
        with self._lock:
            return dict(
                read_bytes=sum(b.read_bytes for b in self._buckets.values()),
                write_bytes=sum(b.write_bytes for b in self._buckets.values()),
                read_ops=sum(b.read_ops for b in self._buckets.values()),
                write_ops=sum(b.write_ops for b in self._buckets.values()),
            )

    def to_csv(self) -> str:
        rows = self.timeline()
        out = ["t_s,read_mb_s,write_mb_s,read_ops,write_ops"]
        for r in rows:
            out.append(
                f"{r['t']:.1f},{r['read_mb']:.3f},{r['write_mb']:.3f},"
                f"{r['read_ops']},{r['write_ops']}"
            )
        return "\n".join(out)


@dataclass
class StepTimer:
    """Per-step wall-clock decomposition used by the trainer's straggler
    monitor: how long each step spent waiting on data vs. computing.
    ``compute_s`` runs from the step's call to its metrics on the host:
    ``dispatch_s`` (the jitted call until it returns) plus ``sync_s``
    (bringing the metrics to the host, which waits for the device) plus the
    little Python around them."""

    data_wait_s: List[float] = field(default_factory=list)
    dispatch_s: List[float] = field(default_factory=list)
    sync_s: List[float] = field(default_factory=list)
    compute_s: List[float] = field(default_factory=list)
    checkpoint_s: List[float] = field(default_factory=list)

    def summary(self) -> dict:
        import numpy as np

        def stat(xs):
            if not xs:
                return dict(mean=0.0, p50=0.0, p95=0.0, max=0.0, total=0.0)
            a = np.asarray(xs)
            return dict(
                mean=float(a.mean()),
                p50=float(np.percentile(a, 50)),
                p95=float(np.percentile(a, 95)),
                max=float(a.max()),
                total=float(a.sum()),
            )

        return dict(
            data_wait=stat(self.data_wait_s),
            dispatch=stat(self.dispatch_s),
            sync=stat(self.sync_s),
            compute=stat(self.compute_s),
            checkpoint=stat(self.checkpoint_s),
        )
