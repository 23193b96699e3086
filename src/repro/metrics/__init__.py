"""repro.metrics — live telemetry: the always-on half of observability.

:mod:`repro.trace` records spans for post-hoc attribution; this package
answers *live* questions with bounded memory and near-zero disabled cost:

* :mod:`repro.metrics.registry` — the instrument registry.
  :class:`Counter` and :class:`Histogram` (log-bucket sketch: p50/p95/p99
  to <= ``alpha`` relative error without storing samples, mergeable across
  threads) shard per thread; :class:`Gauge` is push-style,
  :class:`FunctionGauge` is polled at collect time.  Module-level
  :func:`inc` / :func:`observe` / :func:`set_gauge` / :func:`add_gauge` /
  :func:`timer` are the hot-path hooks — one global check and a shared
  no-op singleton when metrics are off.
* :mod:`repro.metrics.sampler` — background :class:`Sampler` thread:
  periodic ``collect()`` snapshots into a bounded series + JSONL sink.
* :mod:`repro.metrics.export` — Prometheus text exposition
  (:func:`to_prometheus_text` / :func:`from_prometheus_text`) and lossless
  JSONL snapshots (:func:`dump_jsonl` / :func:`load_jsonl`).
* :mod:`repro.metrics.stall` — :class:`StallDetector`: rolling-percentile
  step-duration watchdog that dumps a metrics+trace snapshot when tripped.

Instrumented producers: ``core/readerpool.py`` (size, queue depth,
in-flight), ``core/prefetcher.py`` (occupancy, producer stall, consumer
wait), ``core/dataset.py`` (records, decode latency, drops),
``core/storage.py`` (+ ``faults.py``: per-tier ops/bytes/latency, injected
faults), ``core/engine.py`` (pending saves, snapshot/write/drain latency, drain
backlog bytes),
``train/trainer.py`` (per-step heartbeat + stall detection).

Typical use::

    from repro import metrics

    reg = metrics.start()                    # install global registry
    sampler = metrics.Sampler(interval_s=0.5,
                              jsonl_path="reports/metrics.jsonl").start()
    ...run pipeline / training...
    sampler.stop()
    print(metrics.to_prometheus_text(reg))
    metrics.stop()
"""
from .registry import (
    NULL_METRIC,
    Counter,
    FunctionGauge,
    Gauge,
    Histogram,
    MetricsRegistry,
    add_gauge,
    enabled,
    get_registry,
    hist_quantile,
    inc,
    merge_hist_snapshots,
    observe,
    parse_name,
    register_gauge,
    render_name,
    set_gauge,
    set_registry,
    start,
    stop,
    timer,
    unregister_gauge,
)
from .export import (
    dump_jsonl,
    from_prometheus_text,
    hist_le_buckets,
    load_jsonl,
    series_markdown,
    snapshot_from_json,
    snapshot_to_json,
    to_prometheus_text,
)
from .sampler import Sampler
from .stall import StallDetector, StallEvent

__all__ = [
    # registry
    "MetricsRegistry", "Counter", "Gauge", "FunctionGauge", "Histogram",
    "NULL_METRIC", "hist_quantile", "merge_hist_snapshots",
    "render_name", "parse_name",
    # module-level hooks
    "start", "stop", "enabled", "get_registry", "set_registry",
    "inc", "observe", "set_gauge", "add_gauge", "timer",
    "register_gauge", "unregister_gauge",
    # export
    "to_prometheus_text", "from_prometheus_text", "hist_le_buckets",
    "dump_jsonl", "load_jsonl", "snapshot_to_json", "snapshot_from_json",
    "series_markdown",
    # sampler / stall
    "Sampler", "StallDetector", "StallEvent",
]
