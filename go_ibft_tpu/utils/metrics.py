"""Minimal metrics registry.

The reference emits exactly one gauge family via armon/go-metrics
(core/ibft.go:138-141): ``go-ibft.{sequence|round}.duration``.  This registry
keeps that surface (plus histograms used by the batch verifier for per-batch
device latency) without external dependencies; an embedder can attach a sink
to export to Prometheus or anything else.

**Fixed-bucket latency histograms** (cross-process telemetry plane): the
windowed deques above lose history and cannot be scraped incrementally, so
the live ``/metrics`` endpoint (:mod:`go_ibft_tpu.obs.metrics_export`)
reads a second family — classic Prometheus-style cumulative-bucket
histograms recorded at the hot seams (accept->finalize, verify drains per
route, per-tenant scheduler drains, proof serving, WAL appends).  They are
OFF by default behind one module-global predicate, exactly like the trace
recorder: a disabled ``observe_fixed`` site costs one attribute read.
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from collections import defaultdict, deque
from typing import Callable, Optional, Sequence

# Bounded so a long-running validator (many samples per round, forever)
# cannot leak memory; scrapers wanting full fidelity attach a sink.
_HISTOGRAM_WINDOW = 4096

_lock = threading.Lock()
_gauges: dict[tuple[str, ...], float] = {}
_histograms: dict[tuple[str, ...], deque[float]] = defaultdict(
    lambda: deque(maxlen=_HISTOGRAM_WINDOW)
)
_counters: dict[tuple[str, ...], int] = defaultdict(int)
_sink: Optional[Callable[[str, tuple[str, ...], float], None]] = None


def set_sink(sink: Optional[Callable[[str, tuple[str, ...], float], None]]) -> None:
    """Attach a callback receiving (kind, key, value) for every sample."""
    global _sink
    _sink = sink


def set_gauge(key: Sequence[str], value: float) -> None:
    """Set a gauge (reference core/ibft.go:138-141 SetMeasurementTime)."""
    key = tuple(key)
    with _lock:
        _gauges[key] = value
    if _sink is not None:
        _sink("gauge", key, value)


def get_gauge(key: Sequence[str]) -> Optional[float]:
    with _lock:
        return _gauges.get(tuple(key))


def observe(key: Sequence[str], value: float) -> None:
    """Record a histogram sample (e.g. batch-verify kernel latency)."""
    key = tuple(key)
    with _lock:
        _histograms[key].append(value)
    if _sink is not None:
        _sink("histogram", key, value)


def get_histogram(key: Sequence[str]) -> list[float]:
    with _lock:
        return list(_histograms.get(tuple(key), ()))


def inc_counter(key: Sequence[str], n: int = 1) -> int:
    """Increment a monotonic counter (circuit-breaker transitions, quarantined
    lanes, transport retries — the degraded-mode bookkeeping of
    :mod:`go_ibft_tpu.verify` and :mod:`go_ibft_tpu.chaos`).  Returns the new
    value."""
    key = tuple(key)
    with _lock:
        _counters[key] += n
        value = _counters[key]
    if _sink is not None:
        _sink("counter", key, float(value))
    return value


def get_counter(key: Sequence[str]) -> int:
    with _lock:
        return _counters.get(tuple(key), 0)


def gauges_snapshot() -> dict[tuple[str, ...], float]:
    """All gauges (scrape support for the /metrics exposition)."""
    with _lock:
        return dict(_gauges)


def histograms_snapshot() -> dict[tuple[str, ...], list[float]]:
    """All windowed histograms as lists (scrape support)."""
    with _lock:
        return {k: list(v) for k, v in _histograms.items()}


def counters_snapshot(prefix: Sequence[str] = ()) -> dict[tuple[str, ...], int]:
    """All counters under ``prefix`` (empty prefix = everything)."""
    prefix = tuple(prefix)
    with _lock:
        return {
            k: v for k, v in _counters.items() if k[: len(prefix)] == prefix
        }


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """Sorted-index percentile (no interpolation), ``None`` when empty.

    THE percentile definition for this repo's evidence: /metrics summary
    gauges, the SLO soak records, and the smoke scripts all call this so
    a p99 always means the same sample.
    """
    if not samples:
        return None
    ordered = sorted(samples)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def summarize(key: Sequence[str]) -> Optional[dict]:
    """Histogram summary ``{count, p50, mean, max}`` or ``None`` if empty.

    First-class evidence hook for the packing/pipelining attribution keys
    (``verify/pipeline.py::PACK_MS_KEY`` etc.): bench lines and tests read
    one summary dict instead of re-deriving percentiles from raw samples.
    """
    samples = get_histogram(key)
    if not samples:
        return None
    ordered = sorted(samples)
    return {
        "count": len(ordered),
        "p50": ordered[len(ordered) // 2],
        "mean": sum(ordered) / len(ordered),
        "max": ordered[-1],
    }


def reset() -> None:
    """Clear all recorded metrics (test support)."""
    with _lock:
        _gauges.clear()
        _histograms.clear()
        _counters.clear()
        _fixed.clear()


# ---------------------------------------------------------------------------
# fixed-bucket latency histograms (scrapeable; off unless enabled)
# ---------------------------------------------------------------------------

# Default latency buckets in milliseconds: microsecond WAL appends through
# multi-second degraded drains, roughly x2.5 per step (the Prometheus
# convention), plus the implicit +Inf bucket.
DEFAULT_BUCKETS_MS = (
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
    100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0,
)

# THE predicate: every observe_fixed site checks this one global.
_fixed_enabled = False
_fixed: dict[tuple[str, ...], "_FixedHistogram"] = {}


class _FixedHistogram:
    __slots__ = ("bounds", "counts", "total", "sum")

    def __init__(self, bounds: Sequence[float]) -> None:
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # last = +Inf
        self.total = 0
        self.sum = 0.0


def enable_fixed_histograms() -> None:
    """Turn the fixed-bucket family on (the /metrics mount does this)."""
    global _fixed_enabled
    _fixed_enabled = True


def disable_fixed_histograms() -> None:
    """Back to the no-op path; recorded data stays until :func:`reset`."""
    global _fixed_enabled
    _fixed_enabled = False


def fixed_histograms_enabled() -> bool:
    return _fixed_enabled


def observe_fixed(
    key: Sequence[str],
    value_ms: float,
    bounds: Sequence[float] = DEFAULT_BUCKETS_MS,
) -> None:
    """Record one latency sample into a cumulative-bucket histogram.

    No-op (one global read) unless :func:`enable_fixed_histograms` ran —
    the hot seams call this unconditionally, like ``trace.span``.
    """
    if not _fixed_enabled:
        return
    key = tuple(key)
    with _lock:
        hist = _fixed.get(key)
        if hist is None:
            hist = _fixed[key] = _FixedHistogram(bounds)
        hist.counts[bisect_left(hist.bounds, value_ms)] += 1
        hist.total += 1
        hist.sum += value_ms


def fixed_histograms_snapshot() -> dict[tuple[str, ...], dict]:
    """``{key: {"bounds", "counts", "count", "sum"}}`` — counts are
    per-bucket (not yet cumulative; the Prometheus renderer accumulates)."""
    with _lock:
        return {
            key: {
                "bounds": hist.bounds,
                "counts": tuple(hist.counts),
                "count": hist.total,
                "sum": hist.sum,
            }
            for key, hist in _fixed.items()
        }
