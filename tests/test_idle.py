"""Device idle time charged to program spans (``obs/idle.py``), and the
bridge that puts the spans on the profiler's clock (``obs/trace.py``).

First half: the pure arithmetic of ``idle.attribute`` on hand-built traces
(seconds on one clock; names as the reader hands them over, without the
``ibft:`` / ``bench:`` prefixes).  Second half: real ``jax.profiler``
windows on XLA:CPU, where ring spans must appear as ``ibft:`` host events at
one constant offset, ``/profilez`` must show them with the ring off, and
``merge_device_trace`` must take its shift from them.
"""

import asyncio
import gzip
import json
import time
import urllib.request
from types import SimpleNamespace

import pytest

from go_ibft_tpu.obs import devprof, idle, timeline, trace
from go_ibft_tpu.obs.export import to_chrome_trace
from go_ibft_tpu.obs.httpd import TelemetryServer

MS = 1e-3


def _rows(result, key):
    return {row[0]: row[1] for row in result[key]}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

# One height of 40 ms: the device runs 10-18 and 28-36; the host has
# ingress.flush 8-20 open with verify.drain 9-19 and verify.device_wait
# 10.5-18.5 inside it, commit.drain 26-38 with engine.finalize 37-38 in it.
BUSY = [("jit__recover_fn(1)", 10 * MS, 8 * MS), ("jit__recover_fn(1)", 28 * MS, 8 * MS)]
SPANS = [
    ("ingress.flush", 8 * MS, 12 * MS),
    ("verify.drain", 9 * MS, 10 * MS),
    ("verify.device_wait", 10.5 * MS, 8 * MS),
    ("commit.drain", 26 * MS, 12 * MS),
    ("engine.finalize", 37 * MS, 1 * MS),
]
PHASES = [("prepare_flood", 0.0, 22 * MS), ("commit_flood", 22 * MS, 18 * MS)]


def test_idle_is_charged_to_the_innermost_span_open():
    out = idle.attribute(BUSY, SPANS, PHASES)
    assert out["window_s"] == pytest.approx(40 * MS)
    assert out["busy_s"] == pytest.approx(16 * MS)
    assert out["idle_s"] == pytest.approx(24 * MS)
    by = _rows(out, "idle_by_span")
    # 8-9 and 19-20 flush alone; 9-10 drain before the launch; 10-10.5 is
    # busy; 18-18.5 the wait after the device went quiet; 18.5-19 drain.
    assert by["ingress.flush"] == pytest.approx(2 * MS)
    assert by["verify.drain"] == pytest.approx(1.5 * MS)
    assert by["verify.device_wait"] == pytest.approx(0.5 * MS)
    # 26-28 before the second launch, 36-37 after it; 37-38 finalize.
    assert by["commit.drain"] == pytest.approx(3 * MS)
    assert by["engine.finalize"] == pytest.approx(1 * MS)


def test_idle_with_no_span_open_is_none_split_by_phase():
    out = idle.attribute(BUSY, SPANS, PHASES)
    by = _rows(out, "idle_by_span")
    # 0-8 and 20-22 in prepare_flood; 22-26 and 38-40 in commit_flood.
    assert by["(none):prepare_flood"] == pytest.approx(10 * MS)
    assert by["(none):commit_flood"] == pytest.approx(6 * MS)
    assert "(none)" not in by


def test_idle_outside_every_mark_is_plain_none():
    # No marks: the window is the device's hull, 10-36; the hole 18-28
    # holds commit.drain from 26.
    out = idle.attribute(BUSY, [SPANS[3]], ())
    by = _rows(out, "idle_by_span")
    assert out["window_s"] == pytest.approx(26 * MS)
    assert by == {
        "(none)": pytest.approx(8 * MS),
        "commit.drain": pytest.approx(2 * MS),
    }


@pytest.mark.parametrize(
    "busy,spans,phases",
    [
        (BUSY, SPANS, PHASES),
        (BUSY, SPANS, ()),
        (BUSY, (), PHASES),
        (BUSY[:1], SPANS[:2] + [("ingress.wait", 8.5 * MS, 30 * MS)], PHASES),
    ],
    ids=["marks", "no-marks", "no-spans", "overlap"],
)
def test_idle_rows_sum_to_idle_seconds(busy, spans, phases):
    out = idle.attribute(busy, spans, phases)
    assert sum(row[1] for row in out["idle_by_span"]) == pytest.approx(
        out["idle_s"], rel=1e-9
    )
    assert out["busy_s"] + out["idle_s"] == pytest.approx(out["window_s"])
    # Most first.
    seconds = [row[1] for row in out["idle_by_span"]]
    assert seconds == sorted(seconds, reverse=True)


def test_self_time_with_nested_children():
    out = idle.attribute(BUSY, SPANS, PHASES)
    rows = {row[0]: row for row in out["self_ms_by_span"]}
    assert out["heights"] == 1
    # Duration minus what the children cover, device busy or not.
    assert rows["ingress.flush"][1] == pytest.approx(2.0)
    assert rows["verify.drain"][1] == pytest.approx(2.0)
    assert rows["verify.device_wait"][1] == pytest.approx(8.0)
    assert rows["commit.drain"][1] == pytest.approx(11.0)
    assert rows["engine.finalize"][1] == pytest.approx(1.0)
    assert rows["verify.drain"][2] == 1  # how many spans of the name


def test_a_wait_that_outlives_its_opener_is_charged_from_where_that_closes():
    """``ingress.wait`` opens inside ``engine.send`` (4-6, inside
    ``prepare.drain`` 2-7) and closes at the flush, 9: the span that closes
    first is the one running, so the send keeps 5-6 and the drain 6-7."""
    spans = [
        ("prepare.drain", 2 * MS, 5 * MS),
        ("engine.send", 4 * MS, 2 * MS),
        ("ingress.wait", 5 * MS, 4 * MS),
        ("ingress.flush", 9 * MS, 1 * MS),
        ("engine.finalize", 9.5 * MS, 0.25 * MS),
    ]
    out = idle.attribute((), spans, [("p", 0.0, 10 * MS)])
    rows = {row[0]: row[1] for row in out["self_ms_by_span"]}
    assert rows["prepare.drain"] == pytest.approx(3.0)  # 2-4 and 6-7
    assert rows["engine.send"] == pytest.approx(2.0)
    assert rows["ingress.wait"] == pytest.approx(2.0)  # 7-9 only
    assert rows["ingress.flush"] == pytest.approx(0.75)
    # No device event at all: every instant of the window is idle.
    by = _rows(out, "idle_by_span")
    assert by["ingress.wait"] == pytest.approx(2 * MS)
    assert by["(none):p"] == pytest.approx(2 * MS)


def test_heights_are_the_finalize_spans_that_start_inside_the_window():
    spans = [("engine.finalize", t * MS, 0.5 * MS) for t in (-3, 5, 15, 25, 45)]
    spans.append(("commit.drain", 0.0, 30 * MS))
    out = idle.attribute(
        [("m", 1 * MS, 1 * MS)], spans, [("between_heights", 0.0, 30 * MS)]
    )
    assert out["heights"] == 3
    rows = {row[0]: row for row in out["idle_by_span"]}
    # A height: seconds over three heights, in ms.
    assert rows["engine.finalize"][2] == pytest.approx(0.5)
    assert rows["commit.drain"][2] == pytest.approx((30 - 1 - 1.5) / 3)


def test_no_finalize_no_height_figures():
    out = idle.attribute(BUSY, SPANS[:3], PHASES)
    assert out["heights"] == 0
    assert all(row[2] is None for row in out["idle_by_span"])


def test_longest_gaps_carry_the_stack_open_across_them():
    out = idle.attribute(BUSY, SPANS, PHASES)
    gaps = out["longest_gaps"]
    assert [round(g["seconds"] / MS, 3) for g in gaps] == [10.0, 10.0, 4.0]
    middle = next(g for g in gaps if g["at_s"] == pytest.approx(18 * MS))
    # 18-28: nothing is open across ALL of it; what it was charged to says
    # where it went.
    assert middle["stack"] == []
    assert middle["charged"][0] == ["(none):commit_flood", pytest.approx(4 * MS)]
    last = next(g for g in gaps if g["at_s"] == pytest.approx(36 * MS))
    assert last["phase"] == "commit_flood"
    assert dict(last["charged"])["commit.drain"] == pytest.approx(1 * MS)
    inside = idle.attribute(
        [("m", 0.0, 1 * MS), ("m", 5 * MS, 1 * MS)],
        [("ingress.flush", 0.5 * MS, 5 * MS), ("verify.drain", 0.75 * MS, 4.5 * MS)],
    )
    assert inside["longest_gaps"][0]["stack"] == ["ingress.flush", "verify.drain"]


@pytest.mark.parametrize(
    "offsets_us,one_clock",
    [
        ([1000.2, 1000.9, 1001.4, 1000.0], True),
        ([1000.0, 1019.9], True),
        ([1000.0, 1000.5, 1021.0], False),  # one span 21 us off
        ([1000.0 + 3.0 * i for i in range(40)], False),  # a drifting clock
    ],
    ids=["steady", "at-the-limit", "one-off", "drift"],
)
def test_two_clocks_are_refused_by_their_spread(offsets_us, one_clock):
    pairs = [(5e6 + 100.0 * i + off, 100.0 * i) for i, off in enumerate(offsets_us)]
    out = idle.attribute(BUSY, SPANS, PHASES, pairs)
    clock = out["clock"]
    assert clock["spans"] == len(offsets_us)
    assert clock["spread_us"] == pytest.approx(max(offsets_us) - min(offsets_us))
    assert clock["one_clock"] is one_clock
    if one_clock:
        assert "refused" not in out and out["idle_s"] > 0
        import statistics

        assert clock["offset_us"] == pytest.approx(
            5e6 + statistics.median(offsets_us)
        )
    else:
        # It says so INSTEAD of reporting.
        assert set(out) == {"clock", "refused"}
        assert "not one clock" in out["refused"]


def test_an_empty_trace_is_an_empty_report():
    out = idle.attribute((), (), ())
    assert out["window_s"] == 0.0 and out["idle_by_span"] == []
    assert out["clock"] is None


# ---------------------------------------------------------------------------
# the device plane's lead over the host plane, and the reader, on a
# hand-built xplane (what a TPU trace holds: PERF.md §6, PR 37)
# ---------------------------------------------------------------------------


def test_device_shift_is_the_least_callback_minus_end_over_long_programs():
    busy = [
        ("jit_digest_words(1)", 9.0 * MS, 0.07 * MS),  # short: not matched
        ("jit__recover_fn(2)", 10 * MS, 8 * MS),
        ("jit__recover_fn(2)", 50 * MS, 8 * MS),
    ]
    # The digest's callback, then each recover's, 2.1 and 2.25 ms after the
    # end the device plane gives it.
    done = [9.3 * MS, 20.1 * MS, 49.2 * MS, 60.25 * MS]
    shift = idle.device_shift(busy, done)
    assert shift["programs"] == 2
    assert shift["shift_s"] == pytest.approx(2.1 * MS)
    assert shift["spread_us"] == pytest.approx(150.0)
    # A device plane that lags is moved the other way.
    assert idle.device_shift(busy, [17.5 * MS, 57.6 * MS])["shift_s"] == pytest.approx(
        -0.5 * MS
    )


@pytest.mark.parametrize(
    "busy,done",
    [
        ([("jit__recover_fn(2)", 10 * MS, 8 * MS)], []),  # no callback: the CPU
        ([("jit_digest_words(1)", 9 * MS, 0.07 * MS)], [9.3 * MS]),  # short only
        ([("jit__recover_fn(2)", 10 * MS, 8 * MS)], [40 * MS]),  # another drain's
        ([], [9.3 * MS]),
    ],
    ids=["no-callbacks", "short-programs", "out-of-reach", "no-device"],
)
def test_device_shift_is_none_where_nothing_matches(busy, done):
    assert idle.device_shift(busy, done) is None


def _event(name, start_s, dur_s, **stats):
    return SimpleNamespace(
        name=name,
        start_ns=start_s * 1e9,
        duration_ns=dur_s * 1e9,
        stats=list(stats.items()),
    )


def _xplane(lead_s):
    """One height as a TPU trace has it, the device plane ``lead_s`` early:
    the host stages inputs 10-11.5 ms (``verify.dispatch``), the program
    truly runs 12-20, the runtime's callback comes at 20.05, the wait ends
    at 20.6; a transport thread has a span of its own."""
    lines = lambda **named: [  # noqa: E731
        SimpleNamespace(name=n, events=e) for n, e in named.items()
    ]
    device = SimpleNamespace(
        name="/device:TPU:0",
        lines=[
            SimpleNamespace(
                name="XLA Modules",
                events=[
                    _event("jit_digest_words(1)", 9.5 * MS - lead_s, 0.07 * MS),
                    _event("jit__recover_fn(2)", 12 * MS - lead_s, 8 * MS),
                ],
            ),
            SimpleNamespace(name="XLA Ops", events=[_event("%fusion.1 = x", 0, MS)]),
        ],
    )
    host = SimpleNamespace(
        name="/host:CPU",
        lines=lines(
            python3=[
                _event("bench:commit_flood", 8 * MS, 14 * MS),
                _event("ibft:ingress.flush", 9 * MS, 12 * MS, lanes=99, height=7),
                _event("ibft:verify.pack", 9.1 * MS, 0.9 * MS, ts_us=5_009_100, ts_gap_ns=150),
                _event("ibft:verify.dispatch", 10 * MS, 1.5 * MS, ts_us=5_010_000, ts_gap_ns=140),
                _event("ibft:verify.device_wait", 11.5 * MS, 9.1 * MS, ts_us=5_011_500, ts_gap_ns=90_000),
                _event("ibft:ingress.wait", 8.2 * MS, 0.8 * MS, why="held_back", lanes=99),
                _event("ibft:engine.finalize", 21.2 * MS, 0.3 * MS, height=7),
                _event("PjitFunction(_recover_fn)", 11.2 * MS, 0.3 * MS),
            ],
            futex=[
                _event("tpu::System::Execute=>Done", 9.6 * MS, 0.02 * MS),
                _event("tpu::System::Execute=>Done", 20.05 * MS, 0.05 * MS),
            ],
            grpc=[_event("ibft:net.multicast", 9 * MS, 0.2 * MS)],
        ),
    )
    return SimpleNamespace(planes=[device, host, SimpleNamespace(name="Task Environment", lines=[])])


@pytest.mark.parametrize("lead_ms", [0.0, 0.5, 2.0])
def test_report_moves_the_device_plane_onto_the_host_clock(lead_ms):
    out = idle.report(_xplane(lead_ms * MS))
    # Whatever the device plane's lead, the same charging: the program ends
    # where its completion callback starts.
    assert out["device"] == "/device:TPU:0"
    assert out["device_clock"]["programs"] == 1
    assert out["device_clock"]["shift_s"] == pytest.approx((lead_ms + 0.05) * MS)
    by = _rows(out, "idle_by_span")
    assert by["verify.dispatch"] == pytest.approx(1.5 * MS)  # inputs still staging
    # 0.55 ms of launch before the program, 0.55 of read-back after it.
    assert by["verify.device_wait"] == pytest.approx(1.1 * MS)
    assert out["busy_s"] == pytest.approx(8.07 * MS)
    assert out["heights"] == 1
    # The engine's thread is the one with the span time; the transport
    # thread's span is named, not charged.
    assert out["threads"]["engine"] == "python3"
    assert out["threads"]["others_span_s"] == {"grpc": pytest.approx(0.2 * MS)}
    assert out["waits"] == [["held_back", 1, 1.0, pytest.approx(0.8)]]
    # Two spans carry the ring's start; the third's reads were 90 us apart.
    assert (out["clock"]["spans"], out["clock"]["loose"]) == (2, 1)
    assert out["clock"]["spread_us"] == pytest.approx(0.0, abs=1e-3)


def test_without_callbacks_the_device_plane_is_taken_as_it_is():
    trace_ = _xplane(2.0 * MS)
    trace_.planes[1].lines = [ln for ln in trace_.planes[1].lines if ln.name != "futex"]
    out = idle.report(trace_)
    assert out["device_clock"] is None
    # The run "starts" with its own dispatch span and "ends" 2.6 ms before
    # the wait does: the staging reads as busy, the wait as idle.
    by = _rows(out, "idle_by_span")
    assert "verify.dispatch" not in by
    assert by["verify.device_wait"] == pytest.approx(2.6 * MS)


# ---------------------------------------------------------------------------
# the bridge, in real profiler windows on XLA:CPU
# ---------------------------------------------------------------------------


@pytest.fixture
def ring():
    assert not trace.enabled()
    rec = trace.enable()
    try:
        yield rec
    finally:
        trace.disable()


def _profiled(tmp_path, body):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = idle.newest_xplane(str(tmp_path))
    assert path is not None
    return path


def _workload():
    """Nested spans, a wait that another call closes, an instant."""
    for i in range(20):
        with trace.span("ingress.flush", track="node-x", lanes=99, height=i):
            with trace.span("verify.pack", kind="senders", lanes=99):
                time.sleep(0.0002)
            wait = trace.begin("ingress.wait")
            trace.instant("net.recv", height=i)
        time.sleep(0.0002)
        wait.end(lanes=3, why="held_back", height=i)
        with trace.span("engine.finalize", height=i, round=0):
            pass


def test_ring_spans_are_profiler_events_at_one_constant_offset(tmp_path, ring):
    path = _profiled(tmp_path, _workload)
    loaded = idle.load(path)
    (line,) = loaded["threads"]  # one thread ran them
    events = loaded["threads"][line]
    ring_spans = sorted(
        (r for r in ring.snapshot() if r[0] == "X"), key=lambda r: r[3]
    )
    assert len(events) == len(ring_spans) == 80
    # Instants stay ring-only.
    assert not any(n == "net.recv" for n, _s, _d, _a in events)
    assert sum(1 for r in ring.snapshot() if r[0] == "i") == 20
    # Span for span: the same name, the ring's start as ``ts_us``, the
    # span's scalar args as stats.
    by_ts = {stats["ts_us"]: (n, s, d, stats) for n, s, d, stats in events}
    offsets = []
    for _ph, name, _track, ts_us, dur_us, args in ring_spans:
        n, s, d, stats = by_ts[ts_us]
        assert n == name
        for key, value in (args or {}).items():
            assert stats[key] == value
        # The annotation closes after the ring's end was read.
        assert d * 1e6 >= dur_us - 2
        # A span whose two clock reads were far apart says nothing.
        if stats["ts_gap_ns"] <= idle.MAX_READ_GAP_NS:
            offsets.append(ts_us - s * 1e6)
    assert len(offsets) > 60
    assert max(offsets) - min(offsets) <= idle.MAX_CLOCK_SPREAD_US
    # A wait's late args arrive with its end.
    wait = next(stats for n, _s, _d, stats in events if n == "ingress.wait")
    assert (wait["why"], wait["lanes"]) == ("held_back", 3)

    out = idle.report(path)
    clock = out["clock"]
    assert clock["one_clock"] and clock["spans"] + clock["loose"] == 80
    assert out["heights"] == 20
    assert out["device"] is None and out["busy_s"] == 0.0
    assert out["threads"]["engine"] == line
    assert out["threads"]["others_span_s"] == {}
    ((why, count, a_height, median_ms),) = out["waits"]
    assert (why, count, a_height) == ("held_back", 20, 1.0) and median_ms > 0.2
    # The two sleeps: inside the pack, and under the wait alone.
    names = [row[0] for row in out["self_ms_by_span"]]
    assert set(names[:2]) == {"ingress.wait", "verify.pack"}


def test_the_bridge_alone_with_the_ring_off(tmp_path):
    assert not trace.enabled()
    assert trace.span("x") is trace.NULL_SPAN

    def body():
        with trace.bridged():
            assert not trace.enabled()  # instants and net stamps stay off
            assert trace.recorder() is None
            _workload()
        assert trace.span("x") is trace.NULL_SPAN
        assert trace.begin("x") is trace.NULL_SPAN

    loaded = idle.load(_profiled(tmp_path, body))
    (events,) = loaded["threads"].values()
    assert len(events) == 80
    assert {n for n, _s, _d, _a in events} == {
        "ingress.flush",
        "verify.pack",
        "ingress.wait",
        "engine.finalize",
    }


def test_enable_inside_a_bridged_window_keeps_both(ring):
    with trace.bridged():
        with trace.span("a"):
            pass
    with trace.span("b"):
        pass
    trace.disable()
    assert trace.span("c") is trace.NULL_SPAN
    assert [r[1] for r in ring.snapshot()] == ["a", "b"]


def test_a_span_that_crosses_tasks_keeps_its_track_and_resets_nothing(ring):
    """``begin`` pins no inherited track: its ``end`` runs in another
    task's context, where a contextvar token could not be reset."""
    handle = {}

    async def opener():
        with trace.span("outer", track="node-a"):
            handle["wait"] = trace.begin("engine.wake", track="node-a", type=1)

    async def closer():
        handle["wait"].end(height=7)
        with trace.span("after"):
            pass

    async def main():
        await asyncio.create_task(opener())
        await asyncio.create_task(closer())

    asyncio.run(main())
    spans = {r[1]: r for r in ring.snapshot()}
    assert spans["engine.wake"][2] == "node-a"
    assert spans["engine.wake"][5] == {"type": 1, "height": 7}
    assert spans["after"][2] != "node-a"  # nothing leaked into the closer


def test_profilez_with_the_ring_off_holds_program_spans():
    """An operator's window: a live ``TelemetryServer``, no flight
    recorder; the spans the program closes meanwhile are in the file."""
    assert not trace.enabled()
    server = TelemetryServer(status_fn=lambda: {})
    port = server.start()

    def fetch():
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/profilez?seconds=0.3", timeout=60
        ) as resp:
            return json.loads(resp.read())

    async def program():
        loop = asyncio.get_running_loop()
        fut = loop.run_in_executor(None, fetch)
        while not fut.done():
            with trace.span("commit.drain", height=4, round=0):
                await asyncio.sleep(0.002)
        return await fut

    try:
        payload = asyncio.run(program())
    finally:
        server.stop()
    assert payload["ok"] is True
    assert trace.span("x") is trace.NULL_SPAN  # the window closed the bridge
    out = idle.report(payload["dir"])
    assert out["self_ms_by_span"][0][0] == "commit.drain"
    assert out["self_ms_by_span"][0][2] > 10
    with gzip.open(payload["path"], "rt") as fh:
        names = {e.get("name") for e in json.load(fh)["traceEvents"]}
    assert "ibft:commit.drain" in names


def test_merge_takes_its_shift_from_the_spans_both_documents_hold(tmp_path, ring):
    meta = {}

    def body():
        meta.update(devprof.capture(seconds=0.05, out_dir=str(tmp_path / "w")))

    # ``capture`` sleeps through its window: the spans come from a thread.
    import threading

    def spans():
        time.sleep(0.01)
        for i in range(5):
            with trace.span("verify.drain", track="node-0", lanes=i):
                time.sleep(0.001)

    worker = threading.Thread(target=spans)
    worker.start()
    body()
    worker.join()
    assert meta["ok"], meta
    doc = to_chrome_trace(ring.snapshot())
    merged = timeline.merge_device_trace(
        doc, meta["path"], host_anchor_us=meta["host_anchor_us"]
    )
    other = merged["otherData"]
    assert other["deviceTraceShiftSource"] == "spans"
    assert other["deviceTraceMatchedSpans"] == 5
    assert other["deviceTraceAligned"] is True
    assert other["deviceTraceShiftSpreadUs"] <= idle.MAX_CLOCK_SPREAD_US
    # The profiler's copies of spans the document holds are not doubled.
    assert sum(1 for e in merged["traceEvents"] if "verify.drain" in e["name"]) == 5


def test_merge_falls_back_on_the_anchor_where_no_span_matches(tmp_path):
    host_doc = {
        "otherData": {"clockBaseUs": 1_000_000},
        "traceEvents": [
            {"ph": "X", "pid": 0, "tid": 0, "name": "verify.drain", "ts": 100, "dur": 50}
        ],
    }
    device_doc = {
        "traceEvents": [
            # The same span: ring start 1_000_100, profiler's zero 40 us on.
            {
                "ph": "X",
                "pid": 7,
                "tid": 1,
                "ts": 60.0,
                "dur": 50.0,
                "name": "ibft:verify.drain",
                "args": {"ts_us": "1000100"},
            },
            {"ph": "X", "pid": 7, "tid": 2, "ts": 70.0, "dur": 10.0, "name": "fusion"},
        ]
    }
    path = tmp_path / "dev.trace.json"
    path.write_text(json.dumps(device_doc))
    merged = timeline.merge_device_trace(
        json.loads(json.dumps(host_doc)), str(path), host_anchor_us=1_009_000
    )
    other = merged["otherData"]
    # Exact, from the span; the anchor would have said 9,000.
    assert (other["deviceTraceShiftSource"], other["deviceTraceShiftUs"]) == ("spans", 40)
    assert other["deviceTraceMatchedSpans"] == 1
    fusion = next(e for e in merged["traceEvents"] if e["name"] == "fusion")
    assert fusion["ts"] == 110.0
    # A window whose spans the document does not hold: the anchor, named so.
    device_doc["traceEvents"][0]["args"]["ts_us"] = "999"
    path.write_text(json.dumps(device_doc))
    merged = timeline.merge_device_trace(
        json.loads(json.dumps(host_doc)), str(path), host_anchor_us=1_009_000
    )
    other = merged["otherData"]
    assert (other["deviceTraceShiftSource"], other["deviceTraceShiftUs"]) == ("anchor", 9000)
    assert other["deviceTraceMatchedSpans"] == 0
    assert any(e["name"] == "ibft:verify.drain" for e in merged["traceEvents"])
