"""What both drivers share: the probe a driver marks its phases with, the
compile-event counter, and small helpers over the cost ledger's rows."""

from __future__ import annotations

import math
import sys
import time
from typing import Dict, List, Optional, Tuple


class NullLogger:
    def info(self, *a) -> None:
        pass

    debug = info

    def error(self, msg, *args) -> None:
        print(f"[engine error] {msg} {args}", file=sys.stderr, flush=True)


class CompileEvents:
    """Counts XLA compile requests from JAX's own monitoring events (every
    program, not only the ones a dispatch seam watches).  Copied from
    ``chip_smoke._CompileEvents`` (PR 21)."""

    def __init__(self) -> None:
        import jax

        self.requests = 0
        self.hits = 0
        self.misses = 0

        def on_event(name: str, **_kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(name: str, _secs: float, **_kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return {
            "compile_requests": self.requests,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }


class Probe:
    """The harness's marks inside a driver.

    ``phase(name)`` closes the phase that was open and opens ``name``; at
    every instant of the window exactly one phase is open.  ``boundary()``
    is called by the driver between two operations (heights, calls), when
    nothing is in flight; ``left`` is how many more operations the driver
    has inputs for, where that is bounded (pre-signed heights).  The plain
    probe does nothing with either: it is what a ``--trace 0`` run and the
    tests get.
    """

    def phase(self, name: Optional[str]) -> None:
        pass

    def boundary(self, elapsed_s: float, left: Optional[int] = None) -> None:
        pass

    def wants_more(self) -> bool:
        """Whether the driver should run one more operation although its
        window is over."""
        return False

    def leading(self) -> bool:
        """Whether the next operation is a traced run's lead-in (below): a
        driver that has a shorter piece of the same device work (one
        dispatch, not a whole call) drives that instead and counts it as no
        operation."""
        return False


class TraceProbe(Probe):
    """Marks phases with ``jax.profiler.TraceAnnotation`` (so they are on
    the profiler's clock) and captures one profiler window of at least
    ``trace_seconds`` and ``MIN_OPERATIONS`` whole operations at the end of
    the measured window, started and stopped at operation boundaries (it
    keeps the driver going until it has them: a window of whole operations,
    and ``stop_trace``, which takes most of a minute, delays nothing that is
    measured).  The profiler starts ONE operation before the window: the
    device's op tracing goes live some milliseconds after ``start_trace``
    returns (2 to 13 ms seen, PR 33), and a dispatch running by then loses
    its first ops; so the first operation under the profiler is a lead-in,
    outside the marks, the ledger's edges and every reduction.  The profiler
    starts an operation and ``trace_seconds`` before the measured window's
    end, or earlier where the driver's inputs would run out before that:
    when the operations ``left`` are only the lead-in, those the window
    needs at the pace so far, and two more.  ``on_edge`` is called at both
    edges of the window, with nothing in flight: the harness snapshots the
    cost ledger there."""

    MIN_OPERATIONS = 2

    def __init__(
        self, trace_dir: str, window_s: float, trace_seconds: float, on_edge
    ) -> None:
        self.trace_dir = trace_dir
        self.start_at = max(0.0, window_s - trace_seconds)
        self.trace_seconds = trace_seconds
        self.on_edge = on_edge
        self.state = "before"  # before -> leading -> tracing -> done
        self.started_s = 0.0
        self.boundaries = 0  # seen so far: operations done, but for the first
        self.started_at_boundary = 0
        self.opened_by = None  # "clock" or "operations_left"
        self.operations = None  # whole operations inside the window, once closed
        self.stop_trace_s = None
        self._open = None
        self._name: Optional[str] = None

    def phase(self, name: Optional[str]) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        self._name = name
        if name is not None and self.state == "tracing":
            import jax

            self._open = jax.profiler.TraceAnnotation("bench:" + name)
            self._open.__enter__()

    def _pace(self, elapsed_s: float) -> float:
        """Seconds an operation so far (this call's boundary closes
        operation ``self.boundaries``); 0 before the first has ended."""
        return elapsed_s / self.boundaries if self.boundaries > 0 else 0.0

    def operations_needed(self, elapsed_s: float) -> Optional[int]:
        """The lead-in, the operations the traced window needs at the pace
        so far, and two more; ``None`` before the first operation has shown
        a pace."""
        pace = self._pace(elapsed_s)
        if pace <= 0:
            return None
        window = max(self.MIN_OPERATIONS, math.ceil(self.trace_seconds / pace))
        return 1 + window + 2

    def boundary(self, elapsed_s: float, left: Optional[int] = None) -> None:
        if self.state == "before":
            by_clock = elapsed_s + self._pace(elapsed_s) >= self.start_at
            needed = None if left is None else self.operations_needed(elapsed_s)
            if by_clock or (needed is not None and left <= needed):
                self.opened_by = "clock" if by_clock else "operations_left"
                self._start_profiler()
        elif self.state == "leading":
            self._open_window(elapsed_s)
        elif (
            self.state == "tracing"
            and elapsed_s - self.started_s >= self.trace_seconds
            and self.boundaries - self.started_at_boundary >= self.MIN_OPERATIONS
        ):
            self.stop()
        self.boundaries += 1

    def _start_profiler(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.state = "leading"

    def _open_window(self, elapsed_s: float) -> None:
        self.state = "tracing"
        self.started_s = elapsed_s
        self.started_at_boundary = self.boundaries
        self.on_edge("start")
        self.phase(self._name)  # re-open the phase inside the trace

    def wants_more(self) -> bool:
        return self.state in ("leading", "tracing")

    def leading(self) -> bool:
        return self.state == "leading"

    def stop(self) -> None:
        """Close the profiler window (also called after the window, so a
        driver that ran out of work still yields a trace)."""
        if self.state not in ("leading", "tracing"):
            return
        import jax

        if self.state == "tracing":
            name = self._name
            self.phase(None)
            self._name = name
            self.on_edge("stop")
            self.operations = self.boundaries - self.started_at_boundary
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_trace_s = time.perf_counter() - t0
        self.state = "done"


def ledger_rows(snapshot: Optional[dict]) -> Dict[Tuple[str, str], dict]:
    if not snapshot:
        return {}
    return {(r["program"], r["route"]): r for r in snapshot["dispatches"]}


def ledger_delta(before: Optional[dict], after: Optional[dict]) -> List[dict]:
    """Rows of ``after`` minus ``before``: what a window dispatched."""
    b, a = ledger_rows(before), ledger_rows(after)
    out = []
    for key, row in a.items():
        base = b.get(key, {})
        d = {
            "program": key[0],
            "route": key[1],
            **{
                f: row.get(f, 0) - base.get(f, 0)
                for f in ("dispatches", "live_lanes", "padded_lanes")
            },
        }
        if d["dispatches"] or d["live_lanes"] or d["padded_lanes"]:
            out.append(d)
    return out


def ledger_sum(rows: List[dict], field: str, program="*", route="*") -> int:
    """Sum of ``field`` over the rows that match; ``*`` matches all, and a
    route matches with or without a caller's tag (``serve/device``)."""
    total = 0
    for r in rows:
        if program != "*" and r["program"] != program:
            continue
        if route != "*" and r["route"].rsplit("/", 1)[-1] != route:
            continue
        total += r[field]
    return total
