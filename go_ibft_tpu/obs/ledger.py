"""Process-wide runtime cost ledger: per-dispatch wall-time attribution,
compile-event tracing, and occupancy accounting.

``wall_ms`` is HOST wall time around a dispatch's blocking part, recorded on
every route (host and python included): what the device itself spent comes
from a profiler trace alone (``benchmark/lib/trace_reduce.py``,
``obs/idle.py``).

ISSUE 14 tentpole.  Nine rounds could say *what* ran but not *where
device time or padding went*: the verify plane buckets lanes to power-of-
two shapes (padding waste unmeasured), every subsystem keeps its own
one-off dispatch counter, and a cold XLA compile — the single worst
production number (round 4's ~3-minute ladder-program build) — leaves
no record of which program compiled, when, or for how long.  This module
is the one attributed accounting plane behind all of it:

* **Dispatch records.**  Every jit/shard_map launch seam
  (``verify/batch.py``, ``verify/mesh_batch.py``, ``verify/aggregate.py``,
  ``verify/pipeline.py``, ``sched/dispatch.py``, ``serve/server.py``,
  ``ops/bls12_381.py``, ``net/aggtree.py``) records *program identity*,
  *route*, lane counts split **live vs padded** (occupancy — the fraction
  of a padded bucket doing real work), and wall/block-until-ready
  duration into bounded per-``(program, route)`` accumulators.

* **Program identity IS the compile-budget key space.**  Dispatch records
  use the family names of the ``scripts/compile_budget.py`` registry
  (``ecdsa_recover``, ``digest_words``, ``mesh_verify_mask``,
  ``bls_aggregate_verify``, ``bls_g2_merge_tree``,
  ``bls_multipair_miller``, ...) with the shape suffix dropped — so
  ``scripts/cost_report.py`` can attribute recorded dispatches straight
  onto the pinned program set, and the AOT manifest of ROADMAP item 5
  and this ledger agree on what a "program" is.  A *route* names the
  engine that served the lanes (``device`` / ``mesh`` / ``host`` /
  ``python`` / ``warmup``), optionally prefixed by a caller tag
  (:func:`route_tag`) so e.g. the serve plane's drains read
  ``serve/device``.

* **Compile-event tracing.**  Dispatch spans watch their jit objects'
  compiled-program caches (``PjitFunction._cache_size`` — cold vs warm
  detection by introspection, with the span's wall time as the measured
  first-dispatch duration) and append one record per compilation to an
  append-only ``compile_ledger.jsonl``: program, duration, call-site.
  That file is the precursor manifest for the ROADMAP item 5 AOT cache —
  it lists exactly which programs a process compiled and what each cost.

Disabled mode is ONE predicate check (the :mod:`~go_ibft_tpu.obs.trace`
rule): every instrumentation entry point reads one module global and
returns a shared no-op immediately — no clock reads, no numpy, no lane
counting (``tests/test_cost_ledger.py`` pins the shared no-op).
Thread-safe: accumulators are lock-guarded, the compile log is
flushed per record, and the route tag rides a ``contextvars.ContextVar``
so transport threads and the engine loop never interleave tags.
"""

from __future__ import annotations

import contextvars
import json
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "CostLedger",
    "enable",
    "disable",
    "enabled",
    "get",
    "record_dispatch",
    "add_wall_ms",
    "record_compile",
    "dispatch_span",
    "compile_watch",
    "route_tag",
    "jit_cache_size",
    "snapshot",
    "totals",
    "status",
    "OVERFLOW_PROGRAM",
]

# Bounded key space: a runaway program-name generator (e.g. a bug that
# interpolates a height into the name) must not leak memory; past the cap
# new keys accumulate under one overflow bucket, counted.
DEFAULT_MAX_PROGRAMS = 256
OVERFLOW_PROGRAM = "_other"

# THE predicate: every instrumentation site checks this one global.
_ledger: Optional["CostLedger"] = None

# Caller tag prepended to routes ("serve", "aggtree", ...): set by the
# consuming subsystem around its drains so shared seams attribute to it.
_route_tag: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "go_ibft_ledger_route_tag", default=None
)


def jit_cache_size(fn) -> Optional[int]:
    """Compiled-program count of a ``jax.jit`` object (None when the
    object exposes no cache — plain functions, test stubs, older jax)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:  # noqa: BLE001 - introspection must never raise
        return None


class CostLedger:
    """The accumulator store.  Use the module-level helpers at seams —
    they carry the one-predicate disabled path; this class assumes it is
    live."""

    def __init__(
        self,
        *,
        compile_log: Optional[str] = None,
        max_programs: int = DEFAULT_MAX_PROGRAMS,
    ) -> None:
        self._lock = threading.Lock()
        # (program, route) -> [dispatches, live_lanes, padded_lanes, wall_ms]
        self._stats: Dict[Tuple[str, str], list] = {}
        # program -> [compiles, compile_ms]
        self._compiles: Dict[str, list] = {}
        self._max = max_programs
        self.compile_log_path = compile_log
        self._compile_fh = None
        self.overflowed = 0

    # -- recording ------------------------------------------------------

    @staticmethod
    def _effective_route(route: str) -> str:
        tag = _route_tag.get()
        return route if tag is None else f"{tag}/{route}"

    def _slot(self, program: str, route: str) -> list:
        # Caller holds the lock.
        key = (program, route)
        slot = self._stats.get(key)
        if slot is None:
            if len(self._stats) >= self._max:
                self.overflowed += 1
                key = (OVERFLOW_PROGRAM, OVERFLOW_PROGRAM)
                slot = self._stats.get(key)
                if slot is None:
                    slot = self._stats[key] = [0, 0, 0, 0.0]
                return slot
            slot = self._stats[key] = [0, 0, 0, 0.0]
        return slot

    def record_dispatch(
        self,
        program: str,
        route: str = "device",
        live: int = 0,
        padded: int = 0,
        ms: float = 0.0,
    ) -> None:
        route = self._effective_route(route)
        with self._lock:
            slot = self._slot(program, route)
            slot[0] += 1
            slot[1] += int(live)
            slot[2] += int(padded)
            slot[3] += float(ms)

    def add_wall_ms(self, program: str, route: str, ms: float) -> None:
        """Attribute block-until-ready time to an already-recorded
        dispatch (the async-pipeline path: queue time and wait time are
        observed at different seams)."""
        route = self._effective_route(route)
        with self._lock:
            self._slot(program, route)[3] += float(ms)

    def record_compile(
        self,
        program: str,
        ms: float,
        site: str = "",
        shared_span: int = 1,
    ) -> None:
        """One XLA compilation: accumulate and append to the JSONL log.

        ``shared_span`` > 1 flags that several programs compiled inside
        ONE timed span (a staged pipeline's first dispatch) — ``ms`` is
        then that span's wall split evenly across them (sums stay equal
        to real wall), not an isolated per-program measurement.
        """
        entry = {
            "program": program,
            "ms": round(float(ms), 3),
            "site": site,
            "ts": time.time(),
        }
        if shared_span > 1:
            entry["shared_span"] = shared_span
        with self._lock:
            acc = self._compiles.get(program)
            if acc is None:
                acc = self._compiles[program] = [0, 0.0]
            acc[0] += 1
            acc[1] += float(ms)
            fh = self._ensure_log()
            if fh is not None:
                try:
                    fh.write(json.dumps(entry) + "\n")
                    fh.flush()
                except OSError:
                    pass  # a full disk must never fault a dispatch seam

    def _ensure_log(self):
        # Caller holds the lock.
        if self.compile_log_path is None:
            return None
        if self._compile_fh is None:
            try:
                self._compile_fh = open(self.compile_log_path, "a")
            except OSError:
                self.compile_log_path = None
                return None
        return self._compile_fh

    def close(self) -> None:
        with self._lock:
            if self._compile_fh is not None:
                try:
                    self._compile_fh.close()
                except OSError:
                    pass
                self._compile_fh = None

    # -- reading --------------------------------------------------------

    def snapshot(self) -> dict:
        """Full ledger state: per-(program, route) dispatch rows (sorted
        by wall time, descending) + per-program compile accumulators."""
        with self._lock:
            rows = [
                {
                    "program": program,
                    "route": route,
                    "dispatches": d,
                    "live_lanes": live,
                    "padded_lanes": padded,
                    "wall_ms": round(ms, 3),
                    "occupancy": round(live / padded, 4) if padded else None,
                }
                for (program, route), (d, live, padded, ms) in self._stats.items()
            ]
            compiles = {
                program: {"count": c, "ms": round(ms, 3)}
                for program, (c, ms) in self._compiles.items()
            }
            overflowed = self.overflowed
        rows.sort(key=lambda r: (-r["wall_ms"], -r["dispatches"]))
        return {
            "dispatches": rows,
            "compiles": compiles,
            "overflowed": overflowed,
        }

    @staticmethod
    def _is_warmup_route(route: str) -> bool:
        return route == "warmup" or route.endswith("/warmup")

    def totals(self, include_warmup: bool = False) -> dict:
        """Whole-process sums (the evidence-line stamp source).

        ``route="warmup"`` rows are excluded by default: warmup lanes are
        all-dead by design (live=0), so folding them in would drag every
        totals-derived occupancy (/statusz, evidence ledger blocks, the
        occupancy gates) toward 0 whenever a warmup ran — exactly the
        pollution the dedicated route exists to prevent.  Compile
        accumulators always count (they are per-program, not per-route).
        """
        with self._lock:
            d = live = padded = 0
            ms = 0.0
            for (_program, route), slot in self._stats.items():
                if not include_warmup and self._is_warmup_route(route):
                    continue
                d += slot[0]
                live += slot[1]
                padded += slot[2]
                ms += slot[3]
            compiles = sum(c for c, _ in self._compiles.values())
            compile_ms = sum(m for _, m in self._compiles.values())
        return {
            "dispatches": d,
            "live_lanes": live,
            "padded_lanes": padded,
            "wall_ms": round(ms, 3),
            "compiles": compiles,
            "compile_ms": round(compile_ms, 3),
        }

    def status(self) -> dict:
        """Compact /statusz block: totals + occupancy + the top program
        by attributed wall time."""
        t = self.totals()
        t["occupancy"] = (
            round(t["live_lanes"] / t["padded_lanes"], 4)
            if t["padded_lanes"]
            else None
        )
        with self._lock:
            t["programs"] = len(self._stats)
            production = [
                kv
                for kv in self._stats.items()
                if not self._is_warmup_route(kv[0][1])
            ]
            top = max(production, key=lambda kv: kv[1][3], default=None)
        t["top_program"] = (
            {"program": top[0][0], "route": top[0][1], "wall_ms": round(top[1][3], 3)}
            if top is not None and top[1][3] > 0
            else None
        )
        return t


# ---------------------------------------------------------------------------
# module-level API (the one-predicate seam surface)
# ---------------------------------------------------------------------------


def enable(
    compile_log: Optional[str] = None,
    max_programs: int = DEFAULT_MAX_PROGRAMS,
) -> CostLedger:
    """Install (and return) a fresh ledger; seams start recording.

    ``compile_log`` names the append-only ``compile_ledger.jsonl`` (None
    keeps compile events in memory only)."""
    global _ledger
    if _ledger is not None:
        _ledger.close()
    _ledger = CostLedger(compile_log=compile_log, max_programs=max_programs)
    return _ledger


def disable() -> None:
    """Remove the ledger; every seam reverts to the no-op path."""
    global _ledger
    if _ledger is not None:
        _ledger.close()
    _ledger = None


def enabled() -> bool:
    return _ledger is not None


def get() -> Optional[CostLedger]:
    return _ledger


def record_dispatch(
    program: str,
    route: str = "device",
    live: int = 0,
    padded: int = 0,
    ms: float = 0.0,
) -> None:
    led = _ledger
    if led is None:
        return
    led.record_dispatch(program, route, live, padded, ms)


def add_wall_ms(program: str, route: str, ms: float) -> None:
    led = _ledger
    if led is None:
        return
    led.add_wall_ms(program, route, ms)


def record_compile(
    program: str, ms: float, site: str = "", shared_span: int = 1
) -> None:
    led = _ledger
    if led is None:
        return
    led.record_compile(program, ms, site=site, shared_span=shared_span)


def snapshot() -> Optional[dict]:
    led = _ledger
    return led.snapshot() if led is not None else None


def totals() -> Optional[dict]:
    led = _ledger
    return led.totals() if led is not None else None


def status() -> Optional[dict]:
    led = _ledger
    return led.status() if led is not None else None


class _Null:
    """Shared no-op context manager returned while the ledger is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _RouteTag:
    __slots__ = ("_tag", "_tok")

    def __init__(self, tag: str) -> None:
        self._tag = tag
        self._tok = None

    def __enter__(self):
        self._tok = _route_tag.set(self._tag)
        return self

    def __exit__(self, *exc):
        if self._tok is not None:
            _route_tag.reset(self._tok)
        return False


def route_tag(tag: str):
    """Attribute dispatches recorded within this context to ``tag``
    (routes render as ``tag/route``) — the serve plane and the
    aggregation-tree pump wrap their drains so shared seams split out."""
    if _ledger is None:
        return _NULL
    return _RouteTag(tag)


class _CompileWatch:
    """Times a block and records a compile event per watched jit object
    whose program cache grew inside it."""

    __slots__ = ("_led", "_kernels", "_site", "_before", "_t0")

    def __init__(self, led: CostLedger, kernels, site: str) -> None:
        self._led = led
        self._kernels = tuple(kernels)
        self._site = site

    def __enter__(self):
        self._before = [jit_cache_size(fn) for _name, fn in self._kernels]
        self._t0 = time.perf_counter()
        return self

    def _wall_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3

    def _note_compiles(self, wall_ms: float) -> None:
        grew: List[str] = []
        for (name, fn), n0 in zip(self._kernels, self._before):
            if n0 is None:
                continue
            n1 = jit_cache_size(fn)
            if n1 is not None and n1 > n0:
                grew.append(name)
        # k programs compiling inside ONE timed span share its wall: an
        # even split keeps the SUM (totals, /metrics, evidence deltas)
        # equal to the real wall instead of k-times it; shared_span on
        # each JSONL entry flags that the per-program figure is a split,
        # not an isolated measurement.
        for name in grew:
            self._led.record_compile(
                name,
                wall_ms / len(grew),
                site=self._site,
                shared_span=len(grew),
            )

    def __exit__(self, *exc):
        if not exc or exc[0] is None:
            self._note_compiles(self._wall_ms())
        return False


def compile_watch(kernels: Sequence[tuple], site: str = ""):
    """Watch ``((name, jit_obj), ...)`` for compilations inside the block
    (no dispatch record) — the ops-layer entry points use this so kernel
    identity is attributed where the jit objects live."""
    led = _ledger
    if led is None:
        return _NULL
    return _CompileWatch(led, kernels, site)


class _DispatchSpan(_CompileWatch):
    __slots__ = ("_program", "_route", "_live", "_padded", "_mask", "_block")

    def __init__(
        self, led, program, route, live, padded, live_mask, kernels, block, site
    ):
        super().__init__(led, kernels, site or program)
        self._program = program
        self._route = route
        self._live = live
        self._padded = padded
        self._mask = live_mask
        self._block = block

    def __exit__(self, *exc):
        wall_ms = self._wall_ms()
        # A faulted span still counts its dispatch (a launch happened,
        # and the demote-then-retry ladder SHOULD show as extra launches
        # in the gates) but records no compile event: the wall time of a
        # call that died mid-flight measures nothing a compile table can
        # use.
        if not exc or exc[0] is None:
            self._note_compiles(wall_ms)
        live, padded = self._live, self._padded
        if self._mask is not None:
            import numpy as np

            try:
                mask = np.asarray(self._mask)
                live = int(np.count_nonzero(mask))
                padded = int(mask.size)
            except Exception:  # noqa: BLE001 - an abstract tracer (a seam
                # re-jitted by a caller) has no concrete counts; keep the
                # explicit fallbacks rather than faulting the dispatch.
                pass
        self._led.record_dispatch(
            self._program,
            self._route,
            live,
            padded,
            wall_ms if self._block else 0.0,
        )
        return False


def dispatch_span(
    program: str,
    *,
    route: str = "device",
    live: int = 0,
    padded: int = 0,
    live_mask=None,
    kernels: Sequence[tuple] = (),
    block: bool = True,
    site: str = "",
):
    """The seam instrumentation context manager (no-op unless enabled).

    Records one dispatch for ``program`` on ``route`` at exit.  Lane
    occupancy comes from ``live``/``padded`` counts or, when
    ``live_mask`` is given, from the mask array (padded = its size, live
    = its nonzero count — computed only while the ledger is on).
    ``kernels`` are ``(name, jit_obj)`` pairs watched for compilations
    (jit tracing + XLA compilation run synchronously inside the call, so
    a cache that grew inside the span means this span paid the compile
    and its wall time measures it).  ``block=True`` adds the span's wall
    time to the program's wall_ms (use when the span covers the
    blocking readback); ``block=False`` records the dispatch without
    timing (async queue seams — the readback seam adds the wait via
    :func:`add_wall_ms`).
    """
    led = _ledger
    if led is None:
        return _NULL
    return _DispatchSpan(
        led, program, route, live, padded, live_mask, kernels, block, site
    )
