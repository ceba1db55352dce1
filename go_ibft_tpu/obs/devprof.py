"""On-demand device profiling: jax.profiler trace windows for the ledger.

The cost ledger (:mod:`go_ibft_tpu.obs.ledger`) attributes *wall* time
per program; this module captures what the device itself was doing —
a ``jax.profiler`` window.  The program's spans are IN that window's
file: :func:`capture` turns the tracer's bridge on for its duration
(:func:`go_ibft_tpu.obs.trace.bridged`), flight recorder or no flight
recorder, so every ``trace.span`` is an ``ibft:<name>`` event in the
xplane's host plane, on the device events' clock.
``scripts/idle_report.py <dir>`` (:mod:`go_ibft_tpu.obs.idle`) charges the
device's idle time to them; the Chrome-format output (``*.trace.json.gz``)
also merges into the PR-11 Perfetto document via
:func:`go_ibft_tpu.obs.timeline.merge_device_trace`.

One entry point, :func:`capture`: a fixed-length window (the ``/profilez``
endpoint: ``GET /profilez?seconds=0.5`` on a live
:class:`~go_ibft_tpu.obs.httpd.TelemetryServer`).

It also stamps ``host_anchor_us`` — the flight recorder's monotonic
microsecond clock read after ``start_trace`` returned — which the merge
falls back on where the window and the host document share no span.  It
is a guess: the device's op tracing goes live 2 to 13 ms after
``start_trace`` returns (PERF.md §6, PR 33), and nothing ties the
profiler's zero to that read.

The profiler is a process-global singleton in jax: captures serialize on
a module lock, and a second concurrent request reports ``busy`` instead
of corrupting the open session.  Every failure path returns a dict with
``ok: False`` and a reason — a profiling request must never take down a
telemetry endpoint or a bench run.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import threading
import time
from typing import Optional

from . import trace

__all__ = ["capture", "newest_trace"]

_lock = threading.Lock()

# Anchored capture dir for parameterless captures (the /profilez
# endpoint): ONE per-process directory, pruned before each new window so
# a scraper polling /profilez forever holds at most one trace on disk.
# Callers that pass their own out_dir own its lifecycle.
_default_dir: Optional[str] = None

MIN_SECONDS = 0.05
MAX_SECONDS = 30.0


def _default_capture_dir() -> str:
    global _default_dir
    if _default_dir is None or not os.path.isdir(_default_dir):
        _default_dir = tempfile.mkdtemp(prefix="go-ibft-profilez-")
    else:
        # Keep only the latest window: the profiler nests each run under
        # plugins/profile/<timestamp>/ and never reuses one.
        for entry in os.listdir(_default_dir):
            shutil.rmtree(
                os.path.join(_default_dir, entry), ignore_errors=True
            )
    return _default_dir


def newest_trace(out_dir: str) -> Optional[str]:
    """The most recent ``*.trace.json.gz`` under ``out_dir`` (the
    profiler nests runs under ``plugins/profile/<timestamp>/``)."""
    paths = glob.glob(
        os.path.join(out_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        return None
    return max(paths, key=os.path.getmtime)


def _start(out_dir: str) -> Optional[str]:
    """Start a profiler session; returns an error string or None."""
    try:
        import jax

        jax.profiler.start_trace(out_dir)
    except Exception as err:  # noqa: BLE001 - report, never raise
        return f"{type(err).__name__}: {err}"
    return None


def _stop() -> Optional[str]:
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as err:  # noqa: BLE001
        return f"{type(err).__name__}: {err}"
    return None


def capture(seconds: float = 0.5, out_dir: Optional[str] = None) -> dict:
    """Capture one fixed-length profiler window.

    Returns ``{"ok", "dir", "path", "host_anchor_us", "seconds"}`` —
    ``path`` is the Chrome-format trace the window produced (None plus an
    ``error`` when the profiler is unavailable, already busy, or wrote
    nothing); the ``.xplane.pb`` that ``scripts/idle_report.py`` reads lies
    beside it under ``dir``.  Program spans opened and closed inside the
    window are in both.

    Without ``out_dir`` the capture lands in one per-process directory
    that is PRUNED before each new window — a scraper polling /profilez
    holds at most one trace on disk, so copy the file before requesting
    another window.  An explicit ``out_dir`` is never pruned.
    """
    seconds = min(MAX_SECONDS, max(MIN_SECONDS, float(seconds)))
    if not _lock.acquire(blocking=False):
        return {"ok": False, "error": "busy: a profiler window is already open"}
    try:
        out_dir = out_dir or _default_capture_dir()
        with trace.bridged():
            err = _start(out_dir)
            if err is not None:
                return {"ok": False, "error": err, "dir": out_dir}
            anchor_us = time.perf_counter_ns() // 1000
            time.sleep(seconds)
            err = _stop()
        if err is not None:
            return {"ok": False, "error": err, "dir": out_dir}
        path = newest_trace(out_dir)
        meta = {
            "ok": path is not None,
            "dir": out_dir,
            "path": path,
            "host_anchor_us": anchor_us,
            "seconds": seconds,
        }
        if path is None:
            meta["error"] = "profiler window produced no .trace.json.gz"
        return meta
    finally:
        _lock.release()
