"""Application metrics API: Counter / Gauge / Histogram.

Analog of ray: python/ray/util/metrics.py (Counter/Gauge/Histogram over the
C++ OpenCensus registry, src/ray/stats/metric_defs.cc).  Metrics are
buffered per process and flushed to the controller KV periodically; the
state API / dashboard reads the aggregated snapshot (the per-node
Prometheus-agent export of the reference, python/ray/_private/
metrics_agent.py, collapses to the controller here).
"""
from __future__ import annotations

import asyncio
import os
import threading
import time
from typing import Sequence

_registry_lock = threading.Lock()
_registry: dict[str, "Metric"] = {}
_flusher: threading.Thread | None = None
FLUSH_PERIOD_S = 2.0


class Metric:
    def __init__(self, name: str, description: str = "",
                 tag_keys: Sequence[str] | None = None):
        if not name:
            raise ValueError("metric name must be non-empty")
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys or ())
        self._default_tags: dict[str, str] = {}
        # (tag tuple) -> value
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()
        with _registry_lock:
            _registry[name] = self
        _ensure_flusher()

    def set_default_tags(self, tags: dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _key(self, tags: dict | None) -> tuple:
        merged = {**self._default_tags, **(tags or {})}
        unknown = set(merged) - set(self.tag_keys)
        if unknown:
            raise ValueError(f"unknown tag keys {unknown}; declared "
                             f"{self.tag_keys}")
        return tuple(merged.get(k, "") for k in self.tag_keys)

    def remove(self, tags: dict | None = None) -> None:
        """Drop one tagged series from this metric.  Short-lived tag
        values (a per-replica tag under an autoscaler that cycles
        replicas all day) MUST be removed at teardown or the registry —
        and every snapshot riding it: telemetry ring samples, harvest
        replies, /metrics scrapes — grows without bound."""
        k = self._key(tags)
        with self._lock:
            self._values.pop(k, None)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "name": self.name, "description": self.description,
                "type": type(self).__name__.lower(),
                "tag_keys": list(self.tag_keys),
                "values": [
                    {"tags": dict(zip(self.tag_keys, k)), "value": v}
                    for k, v in self._values.items()],
            }


class Counter(Metric):
    """Monotonic counter (ray: util/metrics.py Counter)."""

    def inc(self, value: float = 1.0, tags: dict | None = None) -> None:
        if value < 0:
            raise ValueError("counters only increase")
        k = self._key(tags)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + value


class Gauge(Metric):
    """Last-value gauge (ray: util/metrics.py Gauge)."""

    def set(self, value: float, tags: dict | None = None) -> None:
        with self._lock:
            self._values[self._key(tags)] = float(value)


class Histogram(Metric):
    """Bucketed histogram (ray: util/metrics.py Histogram)."""

    def __init__(self, name: str, description: str = "",
                 boundaries: Sequence[float] | None = None,
                 tag_keys: Sequence[str] | None = None):
        super().__init__(name, description, tag_keys)
        self.boundaries = sorted(boundaries or
                                 [0.001, 0.01, 0.1, 1.0, 10.0, 100.0])
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}

    def observe(self, value: float, tags: dict | None = None) -> None:
        k = self._key(tags)
        with self._lock:
            counts = self._counts.setdefault(
                k, [0] * (len(self.boundaries) + 1))
            i = 0
            while i < len(self.boundaries) and value > self.boundaries[i]:
                i += 1
            counts[i] += 1
            self._sums[k] = self._sums.get(k, 0.0) + value
            self._values[k] = self._sums[k]   # snapshot shows the sum

    def remove(self, tags: dict | None = None) -> None:
        k = self._key(tags)
        with self._lock:
            self._values.pop(k, None)
            self._counts.pop(k, None)
            self._sums.pop(k, None)

    def snapshot(self) -> dict:
        base = super().snapshot()
        with self._lock:
            base["boundaries"] = self.boundaries
            base["counts"] = [
                {"tags": dict(zip(self.tag_keys, k)), "counts": c}
                for k, c in self._counts.items()]
        return base


def get_or_create(cls, name: str, description: str = "",
                  tag_keys: Sequence[str] | None = None, **kwargs):
    """Idempotent metric handle: return the registered metric when one
    of the same name and type exists, else create it.  Library code
    that may instantiate many times per process (e.g. one serve LLM
    engine per replica, many per test run) must use this instead of the
    constructor — re-constructing replaces the registry entry and
    silently drops the accumulated series."""
    with _registry_lock:
        m = _registry.get(name)
    if m is None:
        # The constructor registers itself (under the lock); two racing
        # creators both construct, the registry keeps the last writer —
        # re-read and return THAT one so every caller holds the same
        # handle and no series is silently dropped.
        cls(name, description, tag_keys=tag_keys, **kwargs)
        with _registry_lock:
            m = _registry[name]
    if type(m) is not cls:
        raise TypeError(
            f"metric {name!r} already registered as "
            f"{type(m).__name__}, requested {cls.__name__}")
    return m


def percentile(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over an ALREADY-SORTED sequence (0.0
    for empty) — the one summary-stat helper shared by the trace
    attribution and task-summary surfaces."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def registry_snapshots() -> list[dict]:
    """Snapshot every registered metric under the registry lock — the
    flush loop's walk, shared with the telemetry timeline sampler
    (_private/telemetry.py sample_now)."""
    with _registry_lock:
        return [m.snapshot() for m in _registry.values()]


def _ensure_flusher() -> None:
    """Push local metric snapshots to the controller KV (the metrics-agent
    export path, collapsed)."""
    global _flusher
    with _registry_lock:
        if _flusher is not None:
            return
        _flusher = threading.Thread(target=_flush_loop, daemon=True,
                                    name="metrics-flush")
        _flusher.start()


def _flush_loop() -> None:
    import json

    while True:
        time.sleep(FLUSH_PERIOD_S)
        try:
            from ray_tpu._private import telemetry
            from ray_tpu._private.worker import _global_worker

            core = _global_worker
            flush = core is not None and not core._shutdown.is_set()
            # One module-flag check per period (the failpoints
            # discipline): with the timeline off and no worker to flush
            # to, the loop never walks the registry at all.
            if not (flush or telemetry.ENABLED):
                continue
            snaps = registry_snapshots()
            if not snaps:
                continue
            if telemetry.ENABLED:
                # Timeline sample rides the walk this loop already did
                # — no extra registry locking for the ring.
                telemetry.record_from_snapshots(snaps)
            if not flush:
                continue
            core.call(core.controller_addr, "kv_put",
                      {"ns": "metrics", "key": core.worker_id},
                      [json.dumps({"ts": time.time(),
                                   "metrics": snaps}).encode()],
                      timeout=10.0)
        except (Exception, asyncio.CancelledError):  # noqa: BLE001
            # metrics must never crash work; a cluster shutting down
            # under the call CANCELS it, and CancelledError is no
            # Exception: uncaught it killed this thread for the life of
            # the process, and no later cluster saw a metric
            pass


def _after_fork_child() -> None:
    # The flusher THREAD does not survive fork, but the parent's handle
    # would make _ensure_flusher think it does.  Re-arm the locks FIRST
    # (a fork can land mid-snapshot, leaving the parent's lock state
    # poisoned in the child; the handler runs single-threaded, so
    # replacement is safe), then restart the flusher iff the child
    # inherited a populated registry — a child updating inherited
    # metrics through cached handles never calls a constructor, so
    # nothing else would revive the flush loop or the telemetry
    # sampling that rides it.
    global _flusher, _registry_lock
    _flusher = None
    _registry_lock = threading.Lock()
    for m in _registry.values():
        m._lock = threading.Lock()
    if _registry:
        _ensure_flusher()


os.register_at_fork(after_in_child=_after_fork_child)
