"""Runtime cost ledger (ISSUE 14): dispatch attribution, occupancy,
compile-event tracing, export surfaces, and legacy-counter parity.

Pins the tentpole contracts:

* disabled mode is one predicate — no recording, a shared no-op span;
* dispatch records accumulate per (program, route) with live-vs-padded
  occupancy, bounded key space (overflow bucket, never unbounded);
* compile detection via jit-cache introspection writes one timed JSONL
  entry per cold-compiled program (call-site included);
* route tags prefix the consuming subsystem onto shared-seam records;
* the ledger's counts agree with the legacy ad-hoc counters
  (``multipair_dispatches``, ``merge_dispatches``, sched dispatch
  observations) on a fixed workload — the counter-unification satellite;
* /statusz, /metrics, /profilez and the cost-report renderer all read it.
"""

import gzip
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(
    0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts")
)

from go_ibft_tpu.obs import ledger  # noqa: E402
from go_ibft_tpu.utils import metrics  # noqa: E402


@pytest.fixture(autouse=True)
def _ledger_reset():
    ledger.disable()
    yield
    ledger.disable()


class FakeJit:
    """A jit-shaped object whose compiled-program cache the test grows."""

    def __init__(self):
        self.n = 0

    def _cache_size(self):
        return self.n


# ---------------------------------------------------------------------------
# core accumulators
# ---------------------------------------------------------------------------


def test_disabled_mode_records_nothing_and_shares_one_null_span():
    assert not ledger.enabled()
    ledger.record_dispatch("ecdsa_recover", "device", live=4, padded=8)
    ledger.add_wall_ms("ecdsa_recover", "device", 5.0)
    ledger.record_compile("ecdsa_recover", 100.0)
    assert ledger.snapshot() is None
    assert ledger.totals() is None
    assert ledger.status() is None
    # One shared no-op object for every disabled entry point (the
    # trace._NULL posture: no allocation per call site).
    assert ledger.dispatch_span("x") is ledger.dispatch_span("y")
    assert ledger.compile_watch(()) is ledger.route_tag("z")


def test_dispatch_records_accumulate_with_occupancy():
    ledger.enable()
    ledger.record_dispatch("ecdsa_recover", "device", live=4, padded=8, ms=2.0)
    ledger.record_dispatch("ecdsa_recover", "device", live=8, padded=8, ms=1.0)
    ledger.record_dispatch("ecdsa_recover", "host", live=3, padded=3)
    snap = ledger.snapshot()
    by_key = {(r["program"], r["route"]): r for r in snap["dispatches"]}
    qc = by_key[("ecdsa_recover", "device")]
    assert qc["dispatches"] == 2
    assert qc["live_lanes"] == 12 and qc["padded_lanes"] == 16
    assert qc["occupancy"] == pytest.approx(0.75)
    assert qc["wall_ms"] == pytest.approx(3.0)
    assert by_key[("ecdsa_recover", "host")]["occupancy"] == 1.0
    totals = ledger.totals()
    assert totals["dispatches"] == 3
    assert totals["live_lanes"] == 15 and totals["padded_lanes"] == 19
    status = ledger.status()
    assert status["programs"] == 2
    assert status["top_program"]["program"] == "ecdsa_recover"


def test_totals_exclude_warmup_routes_from_occupancy():
    """Warmup lanes are all-dead by design; totals()/status() occupancy
    must not be dragged toward 0 by a warmup having run."""
    ledger.enable()
    ledger.record_dispatch("ecdsa_recover", "device", live=6, padded=8, ms=1.0)
    ledger.record_dispatch("ecdsa_recover", "warmup", live=0, padded=2048, ms=900.0)
    with ledger.route_tag("serve"):
        ledger.record_dispatch("ecdsa_recover", "warmup", live=0, padded=128)
    totals = ledger.totals()
    assert totals["dispatches"] == 1
    assert totals["padded_lanes"] == 8
    status = ledger.status()
    assert status["occupancy"] == pytest.approx(0.75)
    assert status["top_program"]["program"] == "ecdsa_recover"
    # The per-route snapshot still shows the warmup rows themselves.
    routes = {r["route"] for r in ledger.snapshot()["dispatches"]}
    assert "warmup" in routes and "serve/warmup" in routes
    # Opt-in when the whole-process number is wanted.
    assert ledger.get().totals(include_warmup=True)["dispatches"] == 3


def test_shared_compile_span_wall_splits_not_multiplies(tmp_path):
    """k programs compiling in one span share its wall: accumulated
    compile_ms must equal the span wall, not k times it."""
    import time

    ledger.enable(compile_log=str(tmp_path / "cl.jsonl"))
    a, b = FakeJit(), FakeJit()
    with ledger.compile_watch((("p1", a), ("p2", b)), site="s"):
        a.n += 1
        b.n += 1
        time.sleep(0.01)
    snap = ledger.snapshot()
    total_ms = sum(acc["ms"] for acc in snap["compiles"].values())
    assert 10.0 <= total_ms < 30.0  # ~= one span wall, NOT ~2x


def test_compile_ledger_record_schema_is_pinned(tmp_path):
    """The compile_ledger.jsonl record schema is a cross-process contract:
    boot/aot.py writes it, tests/test_boot.py's second-boot proof and
    scripts/cost_report.py's event table read it.  Exactly ``{program,
    ms, site, ts}`` per record, plus ``shared_span`` only when several
    programs split one timed span."""
    log = tmp_path / "cl.jsonl"
    ledger.enable(compile_log=str(log))
    ledger.record_compile("ecdsa_recover", 120.5, site="tests/schema")
    ledger.record_compile("digest_words", 10.0, site="s2", shared_span=2)
    ledger.disable()
    records = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert len(records) == 2
    assert set(records[0]) == {"program", "ms", "site", "ts"}
    assert records[0]["program"] == "ecdsa_recover"
    assert records[0]["ms"] == 120.5
    assert records[0]["site"] == "tests/schema"
    assert isinstance(records[0]["ts"], float)
    # shared_span is additive-only: present iff the wall was split.
    assert set(records[1]) == {"program", "ms", "site", "ts", "shared_span"}
    assert records[1]["shared_span"] == 2


def test_program_keyspace_is_bounded():
    ledger.enable(max_programs=4)
    for i in range(10):
        ledger.record_dispatch(f"prog-{i}", "device", live=1, padded=1)
    snap = ledger.snapshot()
    assert len(snap["dispatches"]) == 5  # 4 real keys + the overflow bucket
    overflow = [
        r
        for r in snap["dispatches"]
        if r["program"] == ledger.OVERFLOW_PROGRAM
    ]
    assert overflow and overflow[0]["dispatches"] == 6
    assert snap["overflowed"] == 6
    # Totals still count every dispatch — overflow is a naming cap, not
    # a dropped record.
    assert ledger.totals()["dispatches"] == 10


def test_dispatch_span_counts_mask_and_times_block():
    ledger.enable()
    with ledger.dispatch_span(
        "ecdsa_recover",
        route="device",
        live_mask=np.array([True, False, True, False]),
    ):
        pass
    row = ledger.snapshot()["dispatches"][0]
    assert row["live_lanes"] == 2 and row["padded_lanes"] == 4
    assert row["wall_ms"] > 0  # block=True adds the span wall


def test_dispatch_span_detects_compiles_and_logs_jsonl(tmp_path):
    log = tmp_path / "compile_ledger.jsonl"
    ledger.enable(compile_log=str(log))
    warm = FakeJit()
    cold = FakeJit()
    with ledger.dispatch_span(
        "digest_words",
        route="device",
        padded=8,
        kernels=(("digest_words", cold), ("ecdsa_recover", warm)),
        site="tests/test_cost_ledger.py",
    ):
        cold.n += 1  # only this kernel "compiled" inside the span
    snap = ledger.snapshot()
    assert set(snap["compiles"]) == {"digest_words"}
    assert snap["compiles"]["digest_words"]["count"] == 1
    events = [json.loads(line) for line in log.read_text().splitlines()]
    assert len(events) == 1
    event = events[0]
    assert event["program"] == "digest_words"
    assert event["ms"] > 0
    assert event["site"] == "tests/test_cost_ledger.py"
    assert "ts" in event
    # Warm re-dispatch: no new compile event.
    with ledger.dispatch_span(
        "digest_words",
        route="device",
        padded=8,
        kernels=(("digest_words", cold),),
    ):
        pass
    assert ledger.snapshot()["compiles"]["digest_words"]["count"] == 1


def test_shared_span_flag_when_staged_pipeline_compiles_together(tmp_path):
    log = tmp_path / "cl.jsonl"
    ledger.enable(compile_log=str(log))
    a, b = FakeJit(), FakeJit()
    with ledger.compile_watch(
        (("bls_finalexp_easy", a), ("bls_finalexp_hard", b)), site="s"
    ):
        a.n += 1
        b.n += 1
    events = [json.loads(line) for line in log.read_text().splitlines()]
    assert {e["program"] for e in events} == {
        "bls_finalexp_easy",
        "bls_finalexp_hard",
    }
    assert all(e["shared_span"] == 2 for e in events)


def test_route_tag_prefixes_shared_seam_records():
    ledger.enable()
    with ledger.route_tag("serve"):
        ledger.record_dispatch("ecdsa_recover", "device", live=1, padded=1)
    ledger.record_dispatch("ecdsa_recover", "device", live=1, padded=1)
    routes = {r["route"] for r in ledger.snapshot()["dispatches"]}
    assert routes == {"serve/device", "device"}


# ---------------------------------------------------------------------------
# legacy-counter parity (the counter-unification satellite)
# ---------------------------------------------------------------------------


def _bls_lanes(n=2):
    from go_ibft_tpu.crypto import bls as hbls

    keys = [hbls.BLSPrivateKey.from_seed(b"parity-%d" % i) for i in range(2)]
    msg = b"ledger parity lane" + b"\x00" * 14
    return [
        (msg, [k.sign(msg) for k in keys], [k.pubkey for k in keys])
    ] * n


def test_multipair_ledger_counts_match_legacy_counters():
    """Ledger dispatches/lanes for the multi-pairing program == the
    MULTIPAIR_* counters on a fixed host/python workload (the legacy
    counters stay — /metrics consumers pin them — and the ledger must
    agree so they become redundant reads of one accounting plane)."""
    from go_ibft_tpu.verify.aggregate import (
        MULTIPAIR_DISPATCHES_KEY,
        MULTIPAIR_LANES_KEY,
        multi_aggregate_check,
    )

    ledger.enable()
    lanes = _bls_lanes(2)
    d0 = metrics.get_counter(MULTIPAIR_DISPATCHES_KEY)
    l0 = metrics.get_counter(MULTIPAIR_LANES_KEY)
    assert multi_aggregate_check(lanes, route="host").all()
    assert multi_aggregate_check(lanes, route="python").all()
    d_delta = metrics.get_counter(MULTIPAIR_DISPATCHES_KEY) - d0
    l_delta = metrics.get_counter(MULTIPAIR_LANES_KEY) - l0
    rows = [
        r
        for r in ledger.snapshot()["dispatches"]
        if r["program"] == "bls_multipair_miller"
    ]
    assert sum(r["dispatches"] for r in rows) == d_delta == 2
    assert sum(r["live_lanes"] for r in rows) == l_delta == 4
    assert {r["route"] for r in rows} == {"host", "python"}


def test_merge_tree_ledger_counts_match_legacy_counters(monkeypatch):
    """Device merge dispatches: ledger rows == MERGE_DISPATCHES_KEY /
    MERGE_POINTS_KEY increments (kernel stubbed — counting semantics,
    not compilation, is under test)."""
    from go_ibft_tpu.crypto import bls as hbls
    from go_ibft_tpu.ops import bls12_381 as dev
    from go_ibft_tpu.verify import aggregate as agg

    def fake_tree(sx0, sx1, sy0, sy1, live):
        g = np.shape(live)[0]
        return np.zeros((g, 4, 30), np.int32), np.ones((g,), bool)

    monkeypatch.setattr(dev, "g2_merge_tree", fake_tree)
    ledger.enable()
    points = [hbls.g2_mul(3 + i, hbls.G2_GEN) for i in range(8)]
    d0 = metrics.get_counter(agg.MERGE_DISPATCHES_KEY)
    p0 = metrics.get_counter(agg.MERGE_POINTS_KEY)
    agg._merge_g2_groups_device([points])
    assert metrics.get_counter(agg.MERGE_DISPATCHES_KEY) - d0 == 1
    rows = [
        r
        for r in ledger.snapshot()["dispatches"]
        if r["program"] == "bls_g2_merge_tree"
    ]
    assert sum(r["dispatches"] for r in rows) == 1
    assert (
        sum(r["live_lanes"] for r in rows)
        == metrics.get_counter(agg.MERGE_POINTS_KEY) - p0
        == 8
    )
    # Occupancy exposes the padding the legacy counters never measured:
    # 8 live points in a (1 group x 8 slot) bucket here.
    assert rows[0]["padded_lanes"] == 8


def test_sched_host_flush_parity_with_dispatch_observations():
    """One coalesced host flush == one DISPATCH_LANES_KEY observation ==
    one ledger (ecdsa_recover, host) dispatch."""
    from go_ibft_tpu.messages.helpers import CommittedSeal
    from go_ibft_tpu.sched.dispatch import (
        DISPATCH_LANES_KEY,
        CoalescedDispatcher,
    )

    ledger.enable()
    n0 = len(metrics.get_histogram(DISPATCH_LANES_KEY))
    lanes = [
        (b"\x22" * 32, CommittedSeal(b"\x01" * 20, b"\x03" * 65))
        for _ in range(2)
    ]
    CoalescedDispatcher(route="host").dispatch([], lanes)
    assert len(metrics.get_histogram(DISPATCH_LANES_KEY)) - n0 == 1
    rows = [
        r
        for r in ledger.snapshot()["dispatches"]
        if (r["program"], r["route"]) == ("ecdsa_recover", "host")
    ]
    assert len(rows) == 1 and rows[0]["dispatches"] == 1
    assert rows[0]["live_lanes"] == rows[0]["padded_lanes"] == 2


def test_pipeline_readback_attributes_wall_ms():
    import time

    from go_ibft_tpu.verify.pipeline import VerifyPipeline

    ledger.enable()
    pipe = VerifyPipeline(depth=1, ledger_key=("ecdsa_recover", "device"))
    pipe.run(
        [1, 2],
        pack=lambda item: item,
        dispatch=lambda packed: packed,
        readback=lambda handle: time.sleep(0.002) or handle,
    )
    rows = [
        r
        for r in ledger.snapshot()["dispatches"]
        if (r["program"], r["route"]) == ("ecdsa_recover", "device")
    ]
    assert rows and rows[0]["wall_ms"] >= 2.0


# ---------------------------------------------------------------------------
# export surfaces: /metrics, /statusz, report
# ---------------------------------------------------------------------------


def test_metrics_exposition_renders_ledger_families():
    from go_ibft_tpu.obs import metrics_export

    ledger.enable()
    ledger.record_dispatch("ecdsa_recover", "device", live=6, padded=8, ms=2.5)
    ledger.record_compile("ecdsa_recover", 120.0, site="x")
    series = metrics_export.parse_exposition(
        metrics_export.render_prometheus()
    )
    labels = '{program="ecdsa_recover",route="device"}'
    assert series[f"go_ibft_ledger_dispatches_total{labels}"] == 1
    assert series[f"go_ibft_ledger_lanes_live_total{labels}"] == 6
    assert series[f"go_ibft_ledger_lanes_padded_total{labels}"] == 8
    assert series[f"go_ibft_ledger_occupancy{labels}"] == 0.75
    assert series[f"go_ibft_ledger_wall_ms_total{labels}"] == 2.5
    assert series['go_ibft_ledger_compiles_total{program="ecdsa_recover"}'] == 1
    assert (
        series['go_ibft_ledger_compile_ms_total{program="ecdsa_recover"}']
        == 120.0
    )


def test_cost_report_renderer_and_attribution():
    import cost_report

    families = cost_report.pinned_families()
    # The registry families the seams record under must be pinned —
    # this IS the "registry names are the key space" contract.
    assert {
        "ecdsa_recover",
        "digest_words",
        "mesh_verify_mask",
        "bls_aggregate_verify",
        "bls_g2_merge_tree",
        "bls_multipair_miller",
    } <= families
    snap = {
        "dispatches": [
            {
                "program": "ecdsa_recover",
                "route": "device",
                "dispatches": 19,
                "live_lanes": 100,
                "padded_lanes": 128,
                "wall_ms": 50.0,
                "occupancy": 0.781,
            },
            {
                "program": "mystery_kernel",
                "route": "device",
                "dispatches": 1,
                "live_lanes": 1,
                "padded_lanes": 1,
                "wall_ms": 1.0,
                "occupancy": 1.0,
            },
        ],
        "compiles": {"ecdsa_recover": {"count": 1, "ms": 38000.0}},
        "overflowed": 0,
    }
    report = cost_report.render_snapshot(snap, families=families)
    assert "ecdsa_recover" in report
    assert "mystery_kernel" in report
    assert "95.0%" in report  # 19/20 attributed
    assert "unpinned programs: mystery_kernel" in report
    assert "38000.0" in report


# ---------------------------------------------------------------------------
# device profiling: /profilez + timeline merge
# ---------------------------------------------------------------------------


def test_profilez_endpoint_captures_a_window(tmp_path):
    import urllib.request

    from go_ibft_tpu.obs.httpd import TelemetryServer

    server = TelemetryServer(status_fn=lambda: {})
    port = server.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/profilez?seconds=0.05", timeout=60
        ) as resp:
            assert resp.status == 200
            payload = json.loads(resp.read())
        assert payload["ok"] is True
        assert payload["path"] and payload["path"].endswith(".trace.json.gz")
        assert pathlib.Path(payload["path"]).exists()
        assert payload["host_anchor_us"] > 0
    finally:
        server.stop()


def test_statusz_carries_cost_ledger_block():
    import urllib.request

    from go_ibft_tpu.obs.httpd import TelemetryServer

    ledger.enable()
    ledger.record_dispatch("ecdsa_recover", "device", live=4, padded=8, ms=1.0)
    server = TelemetryServer(status_fn=lambda: {"height": 3})
    port = server.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/statusz", timeout=10
        ) as resp:
            status = json.loads(resp.read())
    finally:
        server.stop()
    assert status["height"] == 3
    block = status["cost_ledger"]
    # The /statusz ledger schema pin (ISSUE 14 satellite).
    assert {
        "dispatches",
        "live_lanes",
        "padded_lanes",
        "wall_ms",
        "compiles",
        "compile_ms",
        "occupancy",
        "programs",
        "top_program",
    } <= set(block)
    assert block["dispatches"] == 1
    assert block["occupancy"] == pytest.approx(0.5)


def test_merge_device_trace_aligns_and_relabels(tmp_path):
    from go_ibft_tpu.obs import timeline

    host_doc = {
        "displayTimeUnit": "ms",
        "otherData": {"droppedRecords": 0, "clockBaseUs": 1_000_000},
        "traceEvents": [
            {
                "ph": "M",
                "pid": 0,
                "tid": 0,
                "name": "thread_name",
                "args": {"name": "node-0"},
            },
            {
                "ph": "X",
                "pid": 0,
                "tid": 0,
                "name": "verify.drain",
                "ts": 100,
                "dur": 50,
                "args": {},
            },
        ],
    }
    device_doc = {
        "displayTimeUnit": "ms",
        "traceEvents": [
            {
                "ph": "M",
                "pid": 701,
                "name": "process_name",
                "args": {"name": "/host:CPU"},
            },
            {"ph": "X", "pid": 701, "tid": 9, "ts": 5, "dur": 10, "name": "fusion"},
            {
                "ph": "X",
                "pid": 701,
                "tid": 9,
                "ts": 20,
                "dur": 1,
                "name": "$python_frame noise",
            },
        ],
    }
    gz = tmp_path / "dev.trace.json.gz"
    with gzip.open(gz, "wt") as fh:
        json.dump(device_doc, fh)
    merged = timeline.merge_device_trace(
        host_doc, str(gz), host_anchor_us=1_000_200
    )
    other = merged["otherData"]
    assert other["deviceTraceAligned"] is True
    assert other["deviceTraceEvents"] == 1  # the $-frame was dropped
    device_events = [
        e for e in merged["traceEvents"] if e.get("pid", 0) != 0
    ]
    names = {e["name"] for e in device_events}
    assert "fusion" in names and "$python_frame noise" not in names
    fusion = next(e for e in device_events if e["name"] == "fusion")
    # anchor (1_000_200) - clockBaseUs (1_000_000) + device ts (5) = 205
    assert fusion["ts"] == 205
    meta = next(
        e
        for e in device_events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    )
    assert meta["args"]["name"] == "device:/host:CPU"
