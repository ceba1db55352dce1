"""Which span had the host open while the device sat idle.

The benchmark's reduction (``benchmark/lib/trace_reduce.py``) splits the
device's idle time by the HARNESS's phases.  This is the program's half:
while tracing is enabled every ``trace.span`` is also a profiler event
named ``ibft:<span>`` (:mod:`go_ibft_tpu.obs.trace`), on the device
events' clock, so one ``.xplane.pb`` holds what the device ran and what the
host had open meanwhile.  ``scripts/idle_report.py`` prints the report.

Two halves, like ``trace_reduce``: :func:`attribute` is pure arithmetic
over plain events ``(name, start_s, dur_s)`` (tier-1 tests it on hand-built
traces) and :func:`load` reads an ``.xplane.pb`` with
``jax.profiler.ProfileData``: the first busy device plane's ``XLA Modules``
line (one event per program execution: the busy intervals), and the host
plane's ``ibft:`` and ``bench:`` events, a line per host thread.

**Innermost.**  Of the spans open at an instant, the one that closes first
(ties: the one opened last).  For nested spans that is the innermost.  A
wait opened with ``trace.begin`` outlives the span it was opened in
(``ingress.wait`` opens inside ``engine.send``'s multicast and closes at
the flush): it is charged from where that span closes, so host work is
never put down to a wait that merely overlaps it.

**Heights** are the ``engine.finalize`` spans that start inside the
window: every "a height" figure divides by that count.

**The device plane is not on the host plane's clock to better than a
millisecond or two.**  The TPU profiler converts device timestamps with an
offset it estimates once a session: in one traced flood a recover run
"started" 20 us into the ``verify.dispatch`` span that stages its inputs
and "ended" 2.0 ms before the runtime's completion callback for it; in
another, 0.5 ms (PERF.md §6, PR 37).  Harness phases do not notice (a
kernel's two edges lie in one phase); spans at a kernel's edges do
(``verify.dispatch`` against ``verify.device_wait``).  The host plane holds
the remedy: the runtime's ``tpu::System::Execute=>Done`` events, on the
host's clock.  A program cannot end after its completion callback starts,
so :func:`device_shift` takes the LEAST ``Done.start - program.end`` over
the window's long programs as the device plane's lead, :func:`report`
moves the busy intervals by it before charging and says so under
``device_clock``.  What stays unknown is the callback's own latency (tens of
microseconds): the programs end that much late.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "attribute",
    "device_shift",
    "load",
    "report",
    "newest_xplane",
    "SPAN_PREFIX",
    "PHASE_PREFIX",
    "NONE",
    "MAX_CLOCK_SPREAD_US",
]

SPAN_PREFIX = "ibft:"
PHASE_PREFIX = "bench:"
NONE = "(none)"
FINALIZE = "engine.finalize"
# Ring start minus profiler start of one span is one constant where the two
# are one clock; the reads are back to back, so a spread over this is not.
MAX_CLOCK_SPREAD_US = 20.0
# A span whose two clock reads were further apart than this (``ts_gap_ns``:
# the thread lost the CPU between them, a collection ran) says nothing about
# the clocks: it is counted under ``clock.loose`` and left out of the spread.
MAX_READ_GAP_NS = 5000
LONGEST_GAPS = 10
# The runtime's completion callback of one program execution, in the host
# plane; programs at least this long are matched to theirs (the short ones
# of a drain, the digest and the join, complete before the long one starts).
_DONE_EVENT = "tpu::System::Execute=>Done"
_LONG_PROGRAM_S = 1e-3
_DONE_WITHIN_S = (-3e-3, 8e-3)  # of the program's end as the device plane has it
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]  # name, start (s), duration (s)


def _merged(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to ``[lo, hi]``."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((s, s + d) for _n, s, d in events if d > 0):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _hull(events: Sequence[Event]) -> Optional[Tuple[float, float]]:
    if not events:
        return None
    return min(s for _n, s, _d in events), max(s + d for _n, s, d in events)


def _clock(pairs: Sequence[Tuple[float, float]]) -> Optional[dict]:
    """``pairs``: (ring ``ts_us``, profiler start in us) of one span each."""
    if not pairs:
        return None
    offsets = [ring - prof for ring, prof in pairs]
    spread = max(offsets) - min(offsets)
    return {
        "spans": len(offsets),
        "offset_us": statistics.median(offsets),
        "spread_us": spread,
        "one_clock": spread <= MAX_CLOCK_SPREAD_US,
    }


def attribute(
    busy: Sequence[Event],
    spans: Sequence[Event],
    phases: Sequence[Event] = (),
    clock_pairs: Sequence[Tuple[float, float]] = (),
) -> dict:
    """Charge the device's idle time to the program's spans.

    ``busy``: the device's program executions; ``spans``: the program's
    spans on the engine's thread, named without the ``ibft:`` prefix;
    ``phases``: the harness's marks, named without ``bench:``;
    ``clock_pairs``: see :func:`_clock`.  All on one clock, in seconds.

    The window is the hull of ``phases`` where there are any, else of
    ``busy``, else of ``spans`` (a trace with no device: self time only).

    Returns ``window`` (start and end on the events' clock), ``window_s``,
    ``busy_s``, ``idle_s``, ``heights``;
    ``idle_by_span`` (``[[name, seconds, ms_per_height], ...]``, most first:
    each idle instant charged to the innermost span open; with none open,
    to ``(none):<phase>``, or plain ``(none)`` where no mark is open: the
    rows sum to ``idle_s``); ``self_ms_by_span`` (``[[name, ms_per_height,
    count], ...]``: the time a span name is innermost, device busy or not);
    ``longest_gaps`` (each ``{"at_s", "seconds", "phase", "stack",
    "charged"}``: the spans open across the whole gap, outermost first, and
    what the gap was charged to); ``clock``.  Where ``clock.spread_us`` is
    over :data:`MAX_CLOCK_SPREAD_US` the ring and the profiler are not one
    clock: the result is ``{"clock", "refused"}`` and nothing else.
    """
    clock = _clock(clock_pairs)
    if clock is not None and not clock["one_clock"]:
        return {
            "clock": clock,
            "refused": (
                f"ring start minus profiler start spreads over "
                f"{clock['spread_us']:.1f} us in {clock['spans']} spans "
                f"(limit {MAX_CLOCK_SPREAD_US:g}): not one clock"
            ),
        }
    window = _hull(phases) or _hull(busy) or _hull(spans)
    if window is None or window[1] <= window[0]:
        return {
            "window": [0.0, 0.0],
            "window_s": 0.0,
            "busy_s": 0.0,
            "idle_s": 0.0,
            "heights": 0,
            "idle_by_span": [],
            "self_ms_by_span": [],
            "longest_gaps": [],
            "clock": clock,
        }
    lo, hi = window
    busy_iv = _merged(busy, lo, hi)
    busy_starts = [s for s, _e in busy_iv]
    busy_s = sum(e - s for s, e in busy_iv)

    live = sorted(
        ((s, s + d, n) for n, s, d in spans if d > 0 and s + d > lo and s < hi),
        key=lambda t: (t[0], -t[1]),
    )
    marks = sorted((s, s + d, n) for n, s, d in phases if d > 0)
    heights = sum(1 for n, s, _d in spans if n == FINALIZE and lo <= s <= hi)

    points = {lo, hi}
    for group in (busy_iv, [(s, e) for s, e, _n in live], [(s, e) for s, e, _n in marks]):
        for s, e in group:
            if lo < s < hi:
                points.add(s)
            if lo < e < hi:
                points.add(e)
    edges = sorted(points)

    def is_busy(t: float) -> bool:
        i = bisect.bisect_right(busy_starts, t) - 1
        return i >= 0 and t < busy_iv[i][1]

    def phase_at(t: float) -> Optional[str]:
        found = None
        for s, e, n in marks:  # a handful a height; the last opened wins
            if s > t:
                break
            if t < e:
                found = n
        return found

    idle: Dict[str, float] = {}
    self_s: Dict[str, float] = {}
    # Per idle gap: [start, end, {charged name: seconds}]
    gaps: List[list] = []
    open_spans: List[Tuple[float, float, str]] = []
    nxt = 0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        while nxt < len(live) and live[nxt][0] <= mid:
            open_spans.append(live[nxt])
            nxt += 1
        open_spans = [sp for sp in open_spans if sp[1] > mid]
        if open_spans:
            name = min(open_spans, key=lambda sp: (sp[1], -sp[0]))[2]
            self_s[name] = self_s.get(name, 0.0) + (b - a)
        else:
            phase = phase_at(mid)
            name = NONE if phase is None else f"{NONE}:{phase}"
        if is_busy(mid):
            continue
        idle[name] = idle.get(name, 0.0) + (b - a)
        if gaps and gaps[-1][1] == a:
            gaps[-1][1] = b
        else:
            gaps.append([a, b, {}])
        charged = gaps[-1][2]
        charged[name] = charged.get(name, 0.0) + (b - a)

    per_height = 1e3 / heights if heights else None

    def a_height(seconds: float) -> Optional[float]:
        return seconds * per_height if per_height is not None else None

    counts: Dict[str, int] = {}
    for _s, _e, n in live:
        counts[n] = counts.get(n, 0) + 1
    longest = []
    for gs, ge, charged in sorted(gaps, key=lambda g: g[0] - g[1])[:LONGEST_GAPS]:
        longest.append(
            {
                "at_s": gs - lo,
                "seconds": ge - gs,
                "phase": phase_at((gs + ge) / 2),
                "stack": [n for s, e, n in live if s <= gs and e >= ge],
                "charged": sorted(
                    ([n, sec] for n, sec in charged.items()), key=lambda r: -r[1]
                ),
            }
        )
    return {
        "window": [lo, hi],
        "window_s": hi - lo,
        "busy_s": busy_s,
        "idle_s": (hi - lo) - busy_s,
        "heights": heights,
        "idle_by_span": [
            [n, sec, a_height(sec)]
            for n, sec in sorted(idle.items(), key=lambda kv: -kv[1])
        ],
        "self_ms_by_span": [
            [n, a_height(sec) if heights else sec * 1e3, counts.get(n, 0)]
            for n, sec in sorted(self_s.items(), key=lambda kv: -kv[1])
        ],
        "longest_gaps": longest,
        "clock": clock,
    }


def device_shift(busy: Sequence[Event], done: Sequence[float]) -> Optional[dict]:
    """How far the device plane leads the host plane, from the runtime's
    completion callbacks.

    ``busy``: the device's program executions as the device plane has them;
    ``done``: starts (s) of the host plane's completion callbacks.  Each
    program of a millisecond or more is matched to the first callback that
    starts within ``_DONE_WITHIN_S`` of its end.  Returns ``{"shift_s",
    "programs", "spread_us"}``: the least ``callback start - program end``
    (add it to the device's timestamps), over how many programs, and how far
    they disagree (the callbacks' own jitter); ``None`` where nothing
    matched (no callback in the trace: another runtime, or the CPU).
    """
    starts = sorted(done)
    leads = []
    for _n, s, d in busy:
        if d < _LONG_PROGRAM_S:
            continue
        end = s + d
        i = bisect.bisect_left(starts, end + _DONE_WITHIN_S[0])
        if i < len(starts) and starts[i] <= end + _DONE_WITHIN_S[1]:
            leads.append(starts[i] - end)
    if not leads:
        return None
    return {
        "shift_s": min(leads),
        "programs": len(leads),
        "spread_us": (max(leads) - min(leads)) * 1e6,
    }


def newest_xplane(path: str) -> Optional[str]:
    """``path`` itself where it is a file, else the newest ``*.xplane.pb``
    under it (the profiler nests runs under ``plugins/profile/<time>/``)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path_or_data) -> dict:
    """Read an xplane file (or the newest under a directory, or a
    ``ProfileData``) into plain events.

    Returns ``{"busy": [Event], "device": plane name or None, "phases":
    [Event], "threads": {line name: [(name, start_s, dur_s, stats)]},
    "done": [start_s]}``:
    ``busy`` is the ``XLA Modules`` line of the first device plane that ran
    anything, ``threads`` the ``ibft:`` events of every host-plane line
    that has one, names without the prefix, ``stats`` the span's scalar
    args and ``ts_us`` (the ring's start of the same span), ``done`` the
    starts of the runtime's completion callbacks (:func:`device_shift`).
    """
    if isinstance(path_or_data, (str, os.PathLike)):
        from jax.profiler import ProfileData

        path = newest_xplane(str(path_or_data))
        if path is None:
            raise FileNotFoundError(f"no .xplane.pb under {path_or_data}")
        data = ProfileData.from_file(path)
    else:
        data = path_or_data
    out: dict = {"busy": [], "device": None, "phases": [], "threads": {}, "done": []}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            if out["device"] is not None:
                continue
            for line in plane.lines:
                if line.name != _MODULES_LINE:
                    continue
                events = [
                    (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in line.events
                ]
                if events:
                    out["busy"], out["device"] = events, plane.name
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == _DONE_EVENT:
                        out["done"].append(e.start_ns / 1e9)
                    elif name.startswith(PHASE_PREFIX):
                        out["phases"].append(
                            (
                                name[len(PHASE_PREFIX) :],
                                e.start_ns / 1e9,
                                e.duration_ns / 1e9,
                            )
                        )
                    elif name.startswith(SPAN_PREFIX):
                        out["threads"].setdefault(line.name, []).append(
                            (
                                name[len(SPAN_PREFIX) :],
                                e.start_ns / 1e9,
                                e.duration_ns / 1e9,
                                {key: value for key, value in e.stats},
                            )
                        )
    out["phases"].sort(key=lambda p: p[1])
    return out


def report(path_or_data) -> dict:
    """:func:`load`, pick the engine's thread, :func:`attribute`.

    The engine's thread is the host-plane line that carries the most
    ``ibft:`` span time (an asyncio engine runs its ingress, its drains and
    its verifier on one loop thread); ``threads`` names it and says how
    much span time the other lines carry, which the charging does not see.
    ``device_clock`` is :func:`device_shift`'s reading, which the busy
    intervals were moved by before the charging (``None``: nothing to go by,
    they are as the device plane has them).
    ``clock`` compares the spans whose two clock reads were back to back
    (``clock.loose`` counts the others).  Adds ``waits``: the window's ``ingress.wait`` spans by ``why``
    (``[[why, count, a_height, median_ms], ...]``): what the held-back rule
    and the calibrated window cost, measured.
    """
    loaded = load(path_or_data)
    totals = {
        line: sum(d for _n, _s, d, _a in events)
        for line, events in loaded["threads"].items()
    }
    engine = max(totals, key=totals.get) if totals else None
    events = loaded["threads"].get(engine, [])
    stamped = [
        (float(stats["ts_us"]), s * 1e6, float(stats.get("ts_gap_ns", 0)))
        for events_ in loaded["threads"].values()
        for _n, s, _d, stats in events_
        if "ts_us" in stats
    ]
    shift = device_shift(loaded["busy"], loaded["done"])
    busy = loaded["busy"]
    if shift is not None:
        busy = [(n, s + shift["shift_s"], d) for n, s, d in busy]
    out = attribute(
        busy,
        [(n, s, d) for n, s, d, _a in events],
        loaded["phases"],
        [(ring, prof) for ring, prof, gap in stamped if gap <= MAX_READ_GAP_NS],
    )
    if out["clock"] is not None:
        out["clock"]["loose"] = sum(1 for *_p, gap in stamped if gap > MAX_READ_GAP_NS)
    out["device"] = loaded["device"]
    out["device_clock"] = shift
    if "window" in out:
        lo, hi = out["window"]
        by_why: Dict[str, List[float]] = {}
        for n, s, d, stats in events:
            if n == "ingress.wait" and lo <= s <= hi:
                by_why.setdefault(str(stats.get("why")), []).append(d * 1e3)
        heights = out["heights"]
        out["waits"] = [
            [why, len(ms), len(ms) / heights if heights else None, statistics.median(ms)]
            for why, ms in sorted(by_why.items())
        ]
    out["threads"] = {
        "engine": engine,
        "engine_span_s": totals.get(engine, 0.0),
        "others_span_s": {
            line: seconds for line, seconds in totals.items() if line != engine
        },
    }
    return out
