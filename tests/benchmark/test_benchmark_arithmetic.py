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
