"""Measured host/device routing constants.

The AdaptiveBatchVerifier's cutover must come from measurement, not
assertion (VERDICT r03 weak #5): the crossover lane count is
``device_dispatch_floor / host_per_verify_cost``, both of which depend on
the actual chip and host CPU.  :func:`save_calibration` persists a
measurement of both; verifier construction reads it.  There is no writer
at present (ROADMAP Queue 1 item 8: the sweep that measures the knees
brings one), so every process runs on the static default below.

The file lives next to the persistent XLA cache — same lifecycle: valid
until the hardware or the kernels change, absent on a fresh checkout (the
verifier then uses a conservative static default).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

_DEFAULT_PATH = os.path.expanduser("~/.cache/go_ibft_tpu/calibration.json")

# Conservative static fallback when no measurement exists (never measured
# on the chip: ROADMAP Queue 1 item 8); a wrong guess here costs latency,
# never correctness (both routes are differential-tested equal).
DEFAULT_CUTOVER_LANES = 16


def _path() -> str:
    return os.environ.get("GO_IBFT_CALIBRATION_FILE", _DEFAULT_PATH)


def load_calibration() -> Optional[dict]:
    """The persisted measurement record, or None."""
    try:
        with open(_path()) as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return None
    return data if isinstance(data, dict) else None


def save_calibration(record: dict) -> None:
    path = _path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(record, fh, indent=1)


def derive_cutover(
    device_floor_ms: float, host_per_verify_ms: float, max_lanes: int
) -> int:
    """Crossover lane count: smallest batch where the (latency-bound,
    lane-count-flat) device dispatch beats ``n`` sequential host verifies."""
    if host_per_verify_ms <= 0:
        return DEFAULT_CUTOVER_LANES
    n = int(device_floor_ms / host_per_verify_ms) + 1
    return max(1, min(n, max_lanes))


# Below this many lanes projected to arrive WITHIN the window ceiling,
# waiting the ceiling is not earning batching — flush eagerly instead.
# Half the default adaptive cutover: a sub-cutover gain is host-routed
# one message at a time anyway.
MIN_GAIN_LANES = 8


def calibrated_window(
    rate_per_s: Optional[float],
    pending: int,
    target: int,
    max_window_s: float,
    min_window_s: float = 0.0,
    min_gain_lanes: int = MIN_GAIN_LANES,
) -> float:
    """The ONE window policy shared by the per-stream calibrator and the
    scheduler's aggregate-rate projection:

    * no measured rate: the ceiling (the conservative prior — exactly
      yesterday's fixed window);
    * the remaining batch projects to fill within the ceiling: wait
      exactly the projection (the batch will genuinely fill; waiting is
      earning batching);
    * it will NOT fill within the ceiling, but the ceiling still gains
      at least ``min_gain_lanes``: wait the ceiling — a sustained
      device-sized flood that fills most-but-not-all of the batch must
      keep coalescing, not collapse to per-message flushes (the cliff a
      naive "too slow -> flush now" rule creates);
    * the ceiling would gain almost nothing (a trickle): flush eagerly
      instead of idling out the window for a handful of lanes.
    """
    if rate_per_s is None or rate_per_s <= 0:
        return max_window_s
    remaining = max(0, target - pending)
    projected = remaining / rate_per_s
    if projected <= max_window_s:
        return max(min_window_s, projected)
    if max_window_s * rate_per_s >= min_gain_lanes:
        return max_window_s
    return min_window_s


class ArrivalCalibrator:
    """EWMA inter-arrival model driving per-stream coalescing windows.

    The fixed 2 ms coalescing window (``BatchingIngress.max_delay``,
    ``TenantScheduler.window_s``) charges every batch the same wait
    regardless of how fast its stream actually arrives — a flood fills
    the batch in microseconds and then idles out the window's tail, a
    trickle waits the full window for company that never comes.  This
    model replaces the constant with a measurement: an exponentially
    weighted mean of inter-arrival gaps (per stream/tenant), fed to
    :func:`calibrated_window` (projection when the batch will fill,
    ceiling when a flood merely can't fill ALL of it, eager only when
    the ceiling would gain almost nothing).

    A wrong estimate costs latency, never correctness: the window only
    decides WHEN a flush fires, and an ``idle_reset_s`` gap drops the
    model back to cold so a stale flood-era estimate cannot linger into
    a quiet period.  Thread-safe (ingress observes from transport
    threads; the scheduler thread reads windows).
    """

    def __init__(
        self,
        *,
        alpha: float = 0.2,
        max_window_s: float = 0.002,
        min_window_s: float = 0.0,
        idle_reset_s: float = 0.25,
    ):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = alpha
        self.max_window_s = max_window_s
        self.min_window_s = min_window_s
        self.idle_reset_s = idle_reset_s
        self._lock = threading.Lock()
        self._last: Optional[float] = None
        self._ewma_dt: Optional[float] = None
        self.observed = 0

    def observe(self, n: int = 1, now: Optional[float] = None) -> None:
        """Record an arrival burst of ``n`` lanes at ``now``."""
        if n <= 0:
            return
        if now is None:
            now = time.monotonic()
        with self._lock:
            if self._last is not None:
                gap = now - self._last
                if gap > self.idle_reset_s:
                    # Idle gap: the old rate is history, not evidence.
                    self._ewma_dt = None
                else:
                    dt = max(gap, 0.0) / n
                    self._ewma_dt = (
                        dt
                        if self._ewma_dt is None
                        else self.alpha * dt + (1 - self.alpha) * self._ewma_dt
                    )
            self._last = now
            self.observed += n

    def rate_per_s(self) -> Optional[float]:
        with self._lock:
            if self._ewma_dt is None or self._ewma_dt <= 0:
                return None
            return 1.0 / self._ewma_dt

    def window(self, pending: int, target: int) -> float:
        """Recommended coalescing wait with ``pending`` lanes already
        buffered toward a ``target``-lane batch (policy:
        :func:`calibrated_window`)."""
        return calibrated_window(
            self.rate_per_s(),
            pending,
            target,
            self.max_window_s,
            self.min_window_s,
        )

    def stats(self) -> dict:
        with self._lock:
            dt = self._ewma_dt
        return {
            "observed": self.observed,
            "ewma_inter_arrival_us": None if dt is None else round(dt * 1e6, 3),
            "rate_per_s": None if dt is None or dt <= 0 else round(1.0 / dt, 1),
        }


def measured_cutover() -> Optional[int]:
    """Cutover from the persisted measurement, if one exists.

    Records measured on a non-TPU platform are ignored: a CPU "device
    floor" is enormous and would derive a cutover that silently disables
    the device path on a later live-TPU run sharing the same home dir.
    (Checked against the record, not ``jax.default_backend()``, so verifier
    construction never initializes a backend: a parent process that only
    builds verifiers must not claim the chip.)
    """
    record = load_calibration()
    if record is None:
        return None
    if record.get("platform") != "tpu":
        return None
    value = record.get("cutover_lanes")
    return value if isinstance(value, int) and value >= 1 else None
