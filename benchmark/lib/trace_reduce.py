"""From a ``jax.profiler`` trace to numbers: the benchmark's reduction.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into a
plain :class:`Trace`; ``reduce`` turns that into device busy time, idle
share, device time per XLA program and per op, and the device's idle time
split by the harness phase that was open (``jax.profiler.TraceAnnotation``
marks written by the drivers, named ``bench:<phase>``: they are on the
profiler's own clock, like the device's events).  The two halves are
separate so that tests check the arithmetic on a hand-built :class:`Trace`
and the reading on a small hand-built xplane.

What a TPU trace looks like (JAX 0.9.0, TPU v5 lite, looked at in PR 22):
one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one
event per program execution, named ``<module>(<fingerprint>)``) and ``XLA
Ops`` (one event per HLO op executed, named by its whole HLO text,
``%copy.5878 = s32[4,128,1200]{...} copy(...)``; a loop is one long ``while``
event with every op of every iteration nested inside it on the same line),
besides ``Async XLA Ops``, ``XLA TraceMe`` and others that are not read; and
the plane ``/host:CPU`` with one line per host thread, ``python`` among them,
where the harness's marks are.  Both are on one clock.  Busy time is a union
of intervals, so nesting counts once.

The recover ladder executes ~216,000 ops a dispatch whatever its lane count,
every one an event: a 128-lane flood fills the profiler's 2 GB buffer in
about one second of device time, after which it DROPS events (it says so in
an ``XLA TraceMe`` event, which ``load`` reports as ``dropped``), and
``stop_trace`` costs about 35 s per million events.  So the drivers' mixes
ask for windows of a height or two, or one ``catch_up`` call.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .stats import merged_intervals

PHASE_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DROPPED_MARK = "Trace Buffers Dropped"
BREAKDOWN_ENTRIES = 10

Event = Tuple[str, float, float]  # name, start (s), duration (s)


@dataclass
class DevicePlane:
    name: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DevicePlane] = field(default_factory=list)
    phases: List[Event] = field(default_factory=list)  # name without prefix
    dropped: bool = False  # the profiler said it dropped events


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path_or_data) -> Trace:
    """Read an xplane file (or a ``ProfileData``) into a :class:`Trace`."""
    if isinstance(path_or_data, (str, os.PathLike)):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path_or_data))
    else:
        data = path_or_data
    trace = Trace()
    names: Dict[str, str] = {}  # an op's whole HLO text -> its short name
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DevicePlane(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    target = dev.ops
                elif line.name == MODULES_LINE:
                    target = dev.modules
                else:
                    if any(e.name == DROPPED_MARK for e in line.events):
                        trace.dropped = True
                    continue
                for e in line.events:
                    raw = e.name
                    name = names.get(raw)
                    if name is None:
                        name = names[raw] = short_op(raw)
                    target.append((name, e.start_ns / 1e9, e.duration_ns / 1e9))
            trace.devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PHASE_PREFIX):
                        trace.phases.append(
                            (
                                e.name[len(PHASE_PREFIX) :],
                                e.start_ns / 1e9,
                                e.duration_ns / 1e9,
                            )
                        )
    trace.phases.sort(key=lambda p: p[1])
    return trace


def short_op(name: str) -> str:
    """``%copy.5878 = s32[4,128,1200]{...} copy(...)`` -> ``copy.5878``."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(short: str) -> str:
    """``copy.5878`` -> ``copy``: the ops of one kind are summed, because
    the ladder spreads its time over hundreds of ops of a few kinds."""
    return re.sub(r"[.\d]+$", "", short) or short


def module_name(event_name: str) -> str:
    """``jit__recover_fn(123456789)`` -> ``jit__recover_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _intervals(events: Sequence[Event]) -> List[Tuple[float, float]]:
    return [(s, s + d) for _n, s, d in events if d > 0]


def _leaf_seconds(ops: Sequence[Event], lo: float, hi: float) -> Dict[str, float]:
    """Device seconds per op name, each instant counted for the innermost
    op open at it (a ``while`` is charged only what its body leaves)."""
    total: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []  # (name, end)
    clock = lo

    def charge(upto: float) -> None:
        nonlocal clock
        upto = min(upto, hi)
        if stack and upto > clock:
            name = stack[-1][0]
            total[name] = total.get(name, 0.0) + (upto - clock)
        clock = max(clock, upto)

    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        e = s + d
        if d <= 0 or e <= lo or s >= hi:
            continue
        while stack and stack[-1][1] <= s:
            charge(stack[-1][1])
            stack.pop()
        charge(s)
        clock = max(clock, s)
        stack.append((name, e))
    while stack:
        charge(stack[-1][1])
        stack.pop()
    return total


def marked_window(trace: Trace) -> Optional[Tuple[float, float]]:
    """From the first harness phase's start to the last one's end: the
    window the reductions keep to (the profiler runs a lead-in operation
    before it, whose first device ops may be missing); ``None`` where the
    trace has no phase mark."""
    if not trace.phases:
        return None
    return (
        min(s for _n, s, _d in trace.phases),
        max(s + d for _n, s, d in trace.phases),
    )


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None) -> dict:
    """The numbers of one traced window.

    ``window`` is ``(start, end)`` on the profiler's clock; by default it
    runs from the first harness phase's start to the last one's end (the
    drivers keep some phase open at every instant of the traced window), or
    over all device events where the trace has no phase mark.

    Returns ``window_s``, ``busy_s`` (union of device-op intervals, averaged
    over the chips that ran anything), ``idle_share``, ``programs``
    (``{module: {"seconds", "runs"}}``, summed over chips), ``device_ops``
    (``[[kind, seconds], ...]``: device seconds by kind of op, ``copy.*``,
    each instant charged to the innermost op open, most first) with
    ``single_ops`` (the same for single ops), ``idle_gaps`` (``[[phase,
    seconds], ...]``: the first chip's idle time by the harness phase open
    during it, most first) with ``longest_gaps`` (``[[phase, seconds], ...]``
    for the single longest gaps), and ``dropped``.
    """
    if window is None:
        window = marked_window(trace)
    if window is None:
        spans = [
            iv for dev in trace.devices for iv in _intervals(dev.ops or dev.modules)
        ]
        if not spans:
            return _empty()
        window = (min(s for s, _e in spans), max(e for _s, e in spans))
    lo, hi = window
    window_s = hi - lo
    if window_s <= 0:
        return _empty()

    busy: List[float] = []
    programs: Dict[str, Dict[str, float]] = {}
    ops_total: Dict[str, float] = {}
    first_busy: Optional[List[Tuple[float, float]]] = None
    for dev in trace.devices:
        source = dev.ops or dev.modules
        merged = merged_intervals(_intervals(source), lo, hi)
        seconds = sum(e - s for s, e in merged)
        if seconds <= 0:
            continue
        busy.append(seconds)
        if first_busy is None:
            first_busy = merged
        for name, s, d in dev.modules:
            cut = min(s + d, hi) - max(s, lo)
            if cut <= 0:
                continue
            rec = programs.setdefault(
                module_name(name), {"seconds": 0.0, "runs": 0}
            )
            rec["seconds"] += cut
            rec["runs"] += 1
        for name, seconds in _leaf_seconds(dev.ops, lo, hi).items():
            ops_total[name] = ops_total.get(name, 0.0) + seconds
    if not busy:
        out = _empty()
        out["window_s"] = window_s
        out["dropped"] = trace.dropped
        return out
    busy_s = sum(busy) / len(busy)
    kinds: Dict[str, float] = {}
    for name, seconds in ops_total.items():
        kind = op_kind(name) + ".*"
        kinds[kind] = kinds.get(kind, 0.0) + seconds

    # Idle time of the first busy chip, split by the phase open during it.
    by_phase: Dict[str, float] = {}
    longest: List[Tuple[str, float]] = []
    cursor = lo
    gaps = []
    for s, e in first_busy + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    for gs, ge in gaps:
        covered = 0.0
        best = ("unmarked", 0.0)
        for name, ps, pd in trace.phases:
            cut = min(ge, ps + pd) - max(gs, ps)
            if cut <= 0:
                continue
            by_phase[name] = by_phase.get(name, 0.0) + cut
            covered += cut
            if cut > best[1]:
                best = (name, cut)
        rest = (ge - gs) - covered
        if rest > 1e-9:
            by_phase["unmarked"] = by_phase.get("unmarked", 0.0) + rest
        longest.append((best[0], ge - gs))
    longest.sort(key=lambda g: -g[1])

    def top(d: Dict[str, float]) -> List[List]:
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:BREAKDOWN_ENTRIES]
        return [[name, seconds] for name, seconds in rows]

    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "chips_busy": len(busy),
        "programs": programs,
        "device_ops": top(kinds),
        "single_ops": top(ops_total),
        "idle_gaps": top(by_phase),
        "longest_gaps": [[n, s] for n, s in longest[:BREAKDOWN_ENTRIES]],
        "dropped": trace.dropped,
    }


def _empty() -> dict:
    return {
        "window_s": 0.0,
        "busy_s": 0.0,
        "idle_share": None,
        "chips_busy": 0,
        "programs": {},
        "device_ops": [],
        "single_ops": [],
        "idle_gaps": [],
        "longest_gaps": [],
        "dropped": False,
    }


def program_seconds(reduced: dict, prefixes: Sequence[str]) -> Tuple[float, int]:
    """Device seconds and runs of the programs whose module name starts
    with one of ``prefixes`` (a jit's module is ``jit_<function name>``)."""
    seconds, runs = 0.0, 0
    for name, rec in reduced.get("programs", {}).items():
        if any(name == p or name.startswith(p) for p in prefixes):
            seconds += rec["seconds"]
            runs += rec["runs"]
    return seconds, runs
