"""The benchmark's own arithmetic: percentiles, interval unions, and the
reduction from a profiler trace to busy time, programs, ops and gaps."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import stats, trace_reduce  # noqa: E402
from benchmark.lib.trace_reduce import DevicePlane, Trace  # noqa: E402


def test_percentile_median_and_p90_interpolate():
    xs = list(range(1, 12))  # 1..11
    assert stats.percentile(xs, 50) == 6
    assert stats.median(xs) == 6
    assert stats.percentile(xs, 90) == 10
    assert stats.percentile([1, 2], 50) == 1.5
    assert stats.percentile([7], 90) == 7
    assert stats.percentile([3, 1, 2], 0) == 1
    assert stats.percentile([3, 1, 2], 100) == 3


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


@pytest.mark.parametrize(
    "n, q, beyond, enough",
    [
        (180, 90, 17, True),  # ~180 heights in a window: p90 is reportable
        (180, 95, 8, False),  # p95 is not: fewer than ten samples beyond it
        (100, 90, 9, False),
        (112, 90, 11, True),
        (20, 50, 9, False),
        (0, 50, 0, False),
    ],
)
def test_ten_samples_beyond_rule(n, q, beyond, enough):
    assert stats.samples_beyond(n, q) == beyond
    assert stats.enough_beyond(n, q) is enough


def test_union_counts_overlap_once_and_clips():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4), (9.0, 9.0)]
    assert stats.union_seconds(ivs) == pytest.approx(3.0)
    assert stats.union_seconds(ivs, 0.75, 3.5) == pytest.approx(1.75)
    assert stats.union_seconds([]) == 0.0


def _trace() -> Trace:
    """Two heights on one chip.  A ``while`` op holds its body ops nested
    on the same line; two programs; gaps inside and between phases."""
    dev = DevicePlane(
        "/device:TPU:0",
        ops=[
            ("while.1", 1.0, 2.0),  # 1..3, body below
            ("fusion.2", 1.0, 0.5),
            ("fusion.3", 2.0, 1.0),
            ("copy.4", 5.0, 1.0),  # 5..6
            ("fusion.2", 8.0, 1.0),  # 8..9
        ],
        modules=[
            ("jit__recover_fn(111)", 1.0, 2.0),
            ("jit_digest_words(222)", 5.0, 1.0),
            ("jit__recover_fn(111)", 8.0, 1.0),
        ],
    )
    phases = [
        ("between_heights", 0.0, 1.0),
        ("prepare_flood", 1.0, 3.5),  # 1..4.5
        ("commit_flood", 4.5, 2.5),  # 4.5..7
        ("between_heights", 7.0, 3.0),  # 7..10
    ]
    return Trace(devices=[dev], phases=phases)


def test_reduce_busy_union_idle_share_and_window():
    r = trace_reduce.reduce(_trace())
    assert r["window_s"] == pytest.approx(10.0)  # first phase start .. last end
    assert r["busy_s"] == pytest.approx(4.0)  # 1..3, 5..6, 8..9: nesting once
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["chips_busy"] == 1


def test_reduce_time_per_program_and_leaf_ops():
    r = trace_reduce.reduce(_trace())
    assert r["programs"]["jit__recover_fn"] == {"seconds": pytest.approx(3.0), "runs": 2}
    assert r["programs"]["jit_digest_words"]["seconds"] == pytest.approx(1.0)
    assert trace_reduce.program_seconds(r, ["jit__recover_fn"]) == (pytest.approx(3.0), 2)
    ops = dict(r["single_ops"])
    # The while is charged only what its body leaves open (1.5..2.0).
    assert ops["while.1"] == pytest.approx(0.5)
    assert ops["fusion.2"] == pytest.approx(1.5)
    assert ops["fusion.3"] == pytest.approx(1.0)
    assert ops["copy.4"] == pytest.approx(1.0)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    assert r["single_ops"][0][0] == "fusion.2"  # most time first
    # The breakdown sums the ops of one kind.
    kinds = dict(r["device_ops"])
    assert kinds == {
        "fusion.*": pytest.approx(2.5),
        "copy.*": pytest.approx(1.0),
        "while.*": pytest.approx(0.5),
    }
    assert r["device_ops"][0][0] == "fusion.*"


def test_reduce_attributes_gaps_to_the_phase_open():
    r = trace_reduce.reduce(_trace())
    gaps = dict(r["idle_gaps"])
    # idle: 0..1 (between), 3..5 (1.5 prepare, 0.5 commit), 6..8 (1 commit,
    # 1 between), 9..10 (between)
    assert gaps["between_heights"] == pytest.approx(3.0)
    assert gaps["prepare_flood"] == pytest.approx(1.5)
    assert gaps["commit_flood"] == pytest.approx(1.5)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    longest = r["longest_gaps"]
    assert longest[0][1] == pytest.approx(2.0)
    assert {g[0] for g in longest[:2]} == {"prepare_flood", "commit_flood", "between_heights"} & {
        g[0] for g in longest[:2]
    }


def test_reduce_explicit_window_clips_everything():
    r = trace_reduce.reduce(_trace(), window=(2.0, 6.0))
    assert r["window_s"] == pytest.approx(4.0)
    assert r["busy_s"] == pytest.approx(2.0)  # 2..3 and 5..6
    assert r["programs"]["jit__recover_fn"]["seconds"] == pytest.approx(1.0)


def test_reduce_without_device_events_reports_no_busy_time():
    r = trace_reduce.reduce(Trace(devices=[DevicePlane("/device:TPU:0")], phases=[("x", 0.0, 1.0)]))
    assert r["busy_s"] == 0.0 and r["idle_share"] is None
    assert r["window_s"] == pytest.approx(1.0)
    assert trace_reduce.reduce(Trace())["window_s"] == 0.0


def test_reduce_averages_busy_over_the_chips_that_ran():
    t = _trace()
    t.devices.append(DevicePlane("/device:TPU:1", ops=[("fusion.9", 0.0, 2.0)]))
    t.devices.append(DevicePlane("/device:TPU:2"))
    r = trace_reduce.reduce(t)
    assert r["chips_busy"] == 2
    assert r["busy_s"] == pytest.approx((4.0 + 2.0) / 2)


XSPACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 2000000 } }
  lines { name: "Steps" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__recover_fn(42)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = s32[4,128,20]{1,0,2:T(4,128)} fusion(s32[4,128,20]{1,0,2:T(4,128)} %p.1), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%while.7 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.2), condition=%c, body=%b" } }
}
planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 200000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:prepare_flood" } }
  event_metadata { key: 2 value { id: 2 name: "bench:between_heights" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(_recover_fn)" } }
}
"""


def test_load_reads_a_small_xplane():
    from jax.profiler import ProfileData

    trace = trace_reduce.load(ProfileData.from_text_proto(XSPACE))
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    dev = trace.devices[0]
    assert [(n, round(s * 1e6, 3), round(d * 1e6, 3)) for n, s, d in dev.ops] == [
        ("fusion.1", 1.0, 1.0),
        ("while.7", 3.0, 2.0),
    ]
    assert dev.modules[0][0] == "jit__recover_fn(42)"
    assert [p[0] for p in trace.phases] == ["prepare_flood", "between_heights"]
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(3e-6)
    assert dict(r["idle_gaps"])["prepare_flood"] == pytest.approx(3e-6)  # 0..1, 2..3, 5..6
    assert dict(r["idle_gaps"])["between_heights"] == pytest.approx(4e-6)
    assert r["programs"]["jit__recover_fn"]["runs"] == 1


def test_load_reports_dropped_events():
    from jax.profiler import ProfileData

    dropped = XSPACE.replace(
        'lines { name: "Steps" timestamp_ns: 1000',
        'lines { name: "XLA TraceMe" timestamp_ns: 1000\n'
        "    events { metadata_id: 4 offset_ps: 0 duration_ps: 1000 } }\n"
        '  event_metadata { key: 4 value { id: 4 name: "Trace Buffers Dropped" } }\n'
        '  lines { name: "Steps" timestamp_ns: 1000',
    )
    assert trace_reduce.load(ProfileData.from_text_proto(XSPACE)).dropped is False
    trace = trace_reduce.load(ProfileData.from_text_proto(dropped))
    assert trace.dropped is True and trace_reduce.reduce(trace)["dropped"] is True


def test_names_are_shortened_as_the_breakdown_gives_them():
    assert trace_reduce.module_name("jit__recover_fn(1234567890)") == "jit__recover_fn"
    assert trace_reduce.module_name("jit_digest_words") == "jit_digest_words"
    hlo = "%copy.5878 = s32[4,128,1200]{1,0,2:T(4,128)S(1)} copy(s32[4,128,1200]{1,2,0} %slice.39997)"
    assert trace_reduce.short_op(hlo) == "copy.5878"
    assert trace_reduce.short_op("fusion.3") == "fusion.3"
    assert trace_reduce.op_kind("copy.5878") == "copy"
    assert trace_reduce.op_kind("get-tuple-element.12") == "get-tuple-element"
    assert trace_reduce.op_kind("wide.region_1.2") == "wide.region_"


# ------------------------------------- the probe's window, the signed heights


class _Profiler:
    """``jax.profiler``'s start and stop, counted and not done."""

    def __init__(self, monkeypatch):
        import jax

        self.calls = []
        monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: self.calls.append("start"))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: self.calls.append("stop"))


def _drive_probe(probe, pace, operations, signed=None):
    """Boundaries as ``round0._drive`` calls them: before each operation,
    with the heights left; goes on while the probe wants more.  Returns how
    many operations ran and whether the signed heights ran out."""
    done = 0
    while True:
        probe.boundary(done * pace, None if signed is None else signed - done)
        if done >= operations and not probe.wants_more():
            return done, False
        if signed is not None and done >= signed:
            return done, True
        done += 1


def test_probe_opens_by_the_clock_where_the_signed_heights_last(monkeypatch):
    from benchmark.lib.harness import TraceProbe

    profiler, edges = _Profiler(monkeypatch), []
    probe = TraceProbe("unused", 20.0, 0.25, edges.append)
    # 1/32 s a height (numbers exact in binary): 640 run in 20 s of 800 signed.
    done, ran_out = _drive_probe(probe, 0.03125, 640, signed=800)
    assert probe.opened_by == "clock" and not ran_out
    # The profiler starts one height before window_s - trace_seconds: that
    # height is the lead-in, and the window's edges are where they were.
    assert probe.started_s == 19.75 and probe.started_at_boundary == 632
    assert probe.operations == 8 and done == 640  # 0.25 s of heights, inside the window
    assert profiler.calls == ["start", "stop"] and edges == ["start", "stop"]


def test_the_first_operation_under_the_profiler_is_a_lead_in_outside_the_window(monkeypatch):
    from benchmark.lib.harness import TraceProbe

    profiler, edges, phases = _Profiler(monkeypatch), [], []
    probe = TraceProbe("unused", 1.0, 0.25, lambda edge: edges.append((edge, list(profiler.calls))))
    monkeypatch.setattr(probe, "phase", lambda name: phases.append((probe.state, name)))
    probe.boundary(0.0)
    probe.boundary(0.5)  # 0.5 + a pace of 0.5 reaches start_at 0.75: the profiler starts
    assert probe.state == "leading" and probe.wants_more() and profiler.calls == ["start"]
    assert edges == [] and phases == []  # no edge, no mark: the lead-in is not the window
    probe.boundary(1.0)  # the lead-in is over: the window opens, with nothing in flight
    assert probe.state == "tracing" and edges == [("start", ["start"])]
    assert probe.started_s == 1.0 and phases == [("tracing", None)]
    probe.boundary(1.5)
    probe.boundary(2.0)
    assert probe.state == "done" and probe.operations == 2
    assert [e for e, _calls in edges] == ["start", "stop"] and profiler.calls == ["start", "stop"]
    # A driver that runs dry inside the lead-in still stops the profiler.
    dry = TraceProbe("unused", 1.0, 0.25, edges.append)
    dry.boundary(0.0)
    dry.boundary(0.75)
    dry.stop()
    assert dry.state == "done" and profiler.calls[-2:] == ["start", "stop"] and len(edges) == 2


def test_probe_opens_by_the_heights_left_before_a_faster_flood_runs_dry(monkeypatch):
    from benchmark.lib.harness import TraceProbe

    profiler, edges = _Profiler(monkeypatch), []
    probe = TraceProbe("unused", 20.0, 0.25, edges.append)
    # 1/64 s a height would use 1280 heights in 20 s; 800 are signed: dry at 12.5 s.
    done, ran_out = _drive_probe(probe, 0.015625, 1280, signed=800)
    assert ran_out and done == 800
    # The lead-in, 0.25 s at 1/64 s (16 heights) and two more: the profiler
    # starts with 19 left and the window opens with 18.
    assert probe.opened_by == "operations_left"
    assert probe.started_at_boundary == 800 - 18 and probe.started_s == 782 * 0.015625
    assert probe.operations == 16 >= TraceProbe.MIN_OPERATIONS and probe.state == "done"
    assert profiler.calls == ["start", "stop"]


def test_probe_with_twelve_signed_heights_still_gets_two_whole_ones(monkeypatch):
    from benchmark.lib.harness import TraceProbe

    profiler = _Profiler(monkeypatch)
    probe = TraceProbe("unused", 20.0, 0.0625, lambda edge: None)  # worth 2 heights of 1/32 s
    done, ran_out = _drive_probe(probe, 0.03125, 640, signed=12)
    assert ran_out and done == 12 and probe.opened_by == "operations_left"
    # With five left: the lead-in, two for the window, two more.
    assert probe.started_at_boundary == 8 and probe.operations == 2 and probe.state == "done"
    # ... and a window never closes on fewer than two operations, however
    # long the first one took.
    slow = TraceProbe("unused", 1.0, 0.25, lambda edge: None)
    slow.boundary(0.0, 100)
    slow.boundary(0.875, 99)  # the profiler starts by the clock: lead-in
    slow.boundary(1.0, 98)  # the window opens
    slow.boundary(1.625, 97)  # 0.625 s later, but one operation
    assert slow.state == "tracing" and slow.wants_more()
    slow.boundary(1.75, 96)
    assert slow.state == "done" and slow.operations == 2
    assert profiler.calls == ["start", "stop", "start", "stop"]


def test_probe_without_a_bound_on_its_inputs_keeps_to_the_clock(monkeypatch):
    from benchmark.lib.harness import Probe, TraceProbe

    _Profiler(monkeypatch)
    probe = TraceProbe("unused", 20.0, 1.0, lambda edge: None)  # driver sync: no ``left``
    done, _ = _drive_probe(probe, 0.25, 80)
    assert probe.opened_by == "clock" and probe.started_s == 19.0
    assert probe.operations == 4 and done == 80
    assert Probe().boundary(1.0, 3) is None and Probe().wants_more() is False


def test_a_driver_may_drive_a_shorter_lead_in_than_a_whole_operation(monkeypatch):
    """Driver ``sync``: under the profiler's first operation it drives one
    chunk, not a call, and counts it as none (``leading()``)."""
    from benchmark.lib.harness import Probe, TraceProbe

    _Profiler(monkeypatch)
    edges = []
    probe = TraceProbe("unused", 2.0, 0.5, edges.append)
    calls, chunks, t = 0, 0, 0.0
    while True:  # ``sync.measure``'s loop: a call is 0.25 s, a chunk 0.0625 s
        probe.boundary(t)
        if t >= 2.0 and not probe.wants_more():
            break
        if probe.leading():
            chunks += 1
            t += 0.0625
            continue
        calls += 1
        t += 0.25
    assert chunks == 1 and probe.operations == 2 and edges == ["start", "stop"]
    assert probe.started_s == 1.3125  # the window opens behind the chunk, not a call later
    assert calls == 8 and Probe().leading() is False


@pytest.mark.parametrize(
    "mix, validators, signed, a_second, loop_ms_now",
    [
        ("flood", 100, 804, 40.0, 40.0),  # finalizes 24.1-24.8 a second (PR 32)
        ("flood-byz30", 300, 444, 22.0, 73.0),  # 13.6 a second (PR 32)
    ],
)
def test_presigned_heights_leave_a_third_of_the_window_unused(mix, validators, signed, a_second, loop_ms_now):
    import json

    from benchmark.drivers import round0

    with open(os.path.join(ROOT, "benchmark", "traffic", mix + ".json")) as fh:
        traffic = json.load(fh)
    assert round0.presigned_heights(traffic, validators, 20.0) == signed
    assert round0.throw_away_heights(traffic) == 1 and round0.throw_away_heights({}) == 0
    window = signed - 1 - traffic["warm_heights"]
    assert window / 20.0 == pytest.approx(a_second)
    used = 20.0 / (loop_ms_now / 1e3)
    assert 1 - used / window >= 0.35  # ISSUE 33: at least 35% unused at today's pace
    assert f"= {signed}" in traffic["presign_rule"]  # the text says today's count
