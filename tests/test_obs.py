"""Observability subsystem: flight recorder, evidence capture, gates.

Pins the ISSUE 4 contracts:

* the span API is thread-safe, ring-bounded, and exports valid Chrome
  ``trace_event`` JSON with one track per node;
* disabled-mode tracing is a single predicate check (a no-op context
  manager — no recorder, no clock reads);
* the evidence writer is append-only JSONL, flushed per record, stamped
  with ``backend``/``probe`` provenance;
* ``bench.py`` that was not told to use the CPU and finds no TPU exits
  non-zero (there is no fallback);
* the regression gates compare fresh evidence against the best prior
  ``BENCH_r*.json`` on the same backend only, direction-aware.
"""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from go_ibft_tpu.obs import evidence, export, gates, trace
from go_ibft_tpu.obs.recorder import RingRecorder

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    trace.disable()


# ---------------------------------------------------------------------------
# recorder + span API
# ---------------------------------------------------------------------------


def test_ring_recorder_bounds_and_order():
    rec = RingRecorder(4)
    for i in range(7):
        rec.append(("i", f"e{i}", "t", i, 0, None))
    assert len(rec) == 4
    assert rec.dropped == 3
    assert [r[1] for r in rec.snapshot()] == ["e3", "e4", "e5", "e6"]
    rec.clear()
    assert len(rec) == 0 and rec.snapshot() == []


def test_span_records_name_track_duration_and_args():
    rec = trace.enable(64)
    with trace.span("outer", track="node-A", round=3):
        time.sleep(0.002)
        with trace.span("inner"):  # inherits the node-A track
            pass
    trace.instant("tick", flavor="x")
    records = rec.snapshot()
    by_name = {r[1]: r for r in records}
    assert by_name["outer"][2] == "node-A"
    assert by_name["inner"][2] == "node-A"  # contextvar inheritance
    assert by_name["outer"][4] >= 2000  # >= 2ms in µs
    assert by_name["outer"][5] == {"round": 3}
    assert by_name["tick"][0] == "i"


def test_span_records_exceptions_and_reraises():
    rec = trace.enable(16)
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    (record,) = rec.snapshot()
    assert record[5]["error"] == "ValueError"


def test_disabled_mode_is_noop_and_cheap():
    assert not trace.enabled()
    span = trace.span("x", lanes=4)
    assert span is trace.span("y")  # the shared null singleton
    with span:
        pass
    trace.instant("z")  # no recorder -> returns immediately


def test_recorder_is_thread_safe():
    rec = trace.enable(10_000)

    def worker(tag):
        for i in range(500):
            with trace.span(f"w{tag}", i=i):
                pass

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec) == 2000


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------


def _validate_trace_doc(doc):
    """The trace_event schema subset both chrome://tracing and Perfetto
    require: a traceEvents list whose entries carry ph/pid/tid/name/ts,
    with dur on complete events and thread_name metadata per tid."""
    assert isinstance(doc["traceEvents"], list)
    named_tids = set()
    for e in doc["traceEvents"]:
        assert e["ph"] in ("M", "X", "i")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert isinstance(e["name"], str)
        assert isinstance(e["args"], dict)
        if e["ph"] == "M":
            assert e["name"] == "thread_name"
            named_tids.add(e["tid"])
        else:
            assert isinstance(e["ts"], int) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    used_tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert used_tids <= named_tids  # every row is labeled
    return doc


def test_export_schema_and_track_metadata(tmp_path):
    rec = trace.enable(128)
    with trace.span("a", track="node-1"):
        pass
    with trace.span("b", track="node-2"):
        trace.instant("mark")
    path = tmp_path / "out.json"
    n = export.write_chrome_trace(str(path), rec)
    doc = _validate_trace_doc(json.loads(path.read_text()))
    assert n == len(doc["traceEvents"])
    names = {e["args"].get("name") for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"node-1", "node-2"} <= names


def test_export_empty_recorder_still_valid(tmp_path):
    path = tmp_path / "empty.json"
    export.write_chrome_trace(str(path), RingRecorder(4))
    assert json.loads(path.read_text())["traceEvents"] == []


def test_export_surfaces_dropped_node_and_clock_offsets(tmp_path):
    """ISSUE 11 satellites: a wrapped ring is visible in the artifact
    (droppedRecords), and a per-node export stamps node identity plus the
    process's clock-offset estimates for the timeline tool."""
    from go_ibft_tpu.obs import clock

    rec = RingRecorder(2)
    for i in range(5):
        rec.append(("i", f"e{i}", "t", i, 0, None))
    clock.reset()
    clock.observe("node-peer", sent_us=1000, recv_us=1400)
    try:
        path = tmp_path / "node.json"
        export.write_chrome_trace(str(path), rec, node="node-me")
        other = json.loads(path.read_text())["otherData"]
        assert other["droppedRecords"] == 3
        assert other["node"] == "node-me"
        assert other["clockOffsetsUs"]["node-peer"]["offset_us"] == 400
    finally:
        clock.reset()


# ---------------------------------------------------------------------------
# engine instrumentation: a multi-node height renders as multi-track
# ---------------------------------------------------------------------------


async def test_cluster_height_emits_per_node_tracks():
    from tests.harness import Cluster

    rec = trace.enable(8192)
    cluster = Cluster(4)
    try:
        await cluster.run_height(0, timeout=5.0)
    finally:
        cluster.shutdown()
    records = rec.snapshot()
    names = {r[1] for r in records}
    assert "round.start" in names and "sequence.done" in names
    assert "prepare.drain" in names and "commit.drain" in names
    node_tracks = {r[2] for r in records if r[1] == "round.start"}
    assert len(node_tracks) == 4  # one timeline row per validator


# ---------------------------------------------------------------------------
# evidence: the append-only writer
# ---------------------------------------------------------------------------

def test_evidence_writer_appends_flushes_and_stamps(tmp_path):
    path = tmp_path / "ev.jsonl"
    with evidence.EvidenceWriter(
        str(path), backend="cpu-fallback", probe="timeout"
    ) as writer:
        writer.record("config_a", {"metric": "config_a", "value": 1.5})
        # flushed per record: the line is on disk BEFORE the writer closes
        lines = path.read_text().splitlines()
        assert len(lines) == 1
        writer.record("config_b", {"metric": "config_b", "value": None})
        assert writer.missing(["config_a", "config_b", "config_c"]) == [
            "config_c"
        ]
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [line["config"] for line in lines] == ["config_a", "config_b"]
    for line in lines:
        for field in evidence.REQUIRED_EVIDENCE_FIELDS:
            assert field in line, (field, line)
        assert line["backend"] == "cpu-fallback"
        assert line["probe"] == "timeout"
    # append-only across writers
    with evidence.EvidenceWriter(str(path), backend="tpu", probe="ok") as w2:
        w2.record("config_c", {"metric": "config_c", "value": 2.0})
    assert len(path.read_text().splitlines()) == 3


# ---------------------------------------------------------------------------
# no fallback: a run that was not told to use the CPU needs the chip
# ---------------------------------------------------------------------------


def test_bench_without_tpu_and_without_cpu_pin_exits_nonzero(tmp_path):
    """``python bench.py`` that was NOT asked for the CPU and finds no TPU
    must exit non-zero, naming the platform it found, before any config
    runs — the CPU schedule is for ``JAX_PLATFORMS=cpu`` only."""
    env = dict(os.environ, GO_IBFT_EVIDENCE_PATH=str(tmp_path / "ev.jsonl"))
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
        env=env,
    )
    assert proc.returncode != 0, proc.stdout + proc.stderr
    lines = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    # the error, then only the run's own ledger summary: no config ran
    assert lines[0]["metric"] == "bench_error", lines
    assert {line["metric"] for line in lines[1:]} <= {"cost_ledger"}, lines
    assert "'cpu'" in lines[0]["error"] and "no TPU" in lines[0]["error"]


# ---------------------------------------------------------------------------
# regression gates
# ---------------------------------------------------------------------------


def _write_prior(tmp_path, name, platform, lines):
    tail = "\n".join(json.dumps(line) for line in lines)
    tail = json.dumps({"metric": "bench_platform", "value": platform}) + "\n" + tail
    (tmp_path / name).write_text(
        json.dumps({"n": 1, "rc": 0, "tail": tail})
    )


def test_gates_direction_aware_pass_warn_fail(tmp_path):
    _write_prior(
        tmp_path,
        "BENCH_r01.json",
        "cpu (fallback: default backend unavailable)",
        [
            {"metric": "lat_ms", "value": 10.0, "unit": "ms"},
            {"metric": "tput", "value": 1000.0, "unit": "sig-verifies/sec"},
            {"metric": "steady", "value": 5.0, "unit": "ms"},
        ],
    )
    # A prior TPU round must NOT gate a CPU-fallback run.
    _write_prior(
        tmp_path,
        "BENCH_r02.json",
        "tpu",
        [{"metric": "lat_ms", "value": 0.001, "unit": "ms"}],
    )
    fresh = [
        {"metric": "bench_platform", "value": "cpu (fallback: x)"},
        {"metric": "lat_ms", "value": 14.0, "unit": "ms"},  # +40% -> fail
        {"metric": "tput", "value": 880.0, "unit": "sig-verifies/sec"},  # -12% -> warn
        {"metric": "steady", "value": 5.2, "unit": "ms"},  # +4% -> pass
        {"metric": "brand_new", "value": 1.0, "unit": "ms"},  # no prior -> info
    ]
    results = {r.config: r for r in gates.gate_evidence(fresh, str(tmp_path))}
    assert results["lat_ms"].status == "fail"
    assert results["lat_ms"].prior == 10.0  # the CPU prior, not the TPU one
    assert results["tput"].status == "warn"
    assert results["steady"].status == "pass"
    assert results["brand_new"].status == "info"
    table = gates.render_table(list(results.values()))
    assert "FAIL" in table and "BENCH_r01.json" in table


def test_gates_best_prior_picks_best_not_latest(tmp_path):
    _write_prior(
        tmp_path,
        "BENCH_r01.json",
        "cpu",
        [{"metric": "lat_ms", "value": 8.0, "unit": "ms"}],
    )
    _write_prior(
        tmp_path,
        "BENCH_r03.json",
        "cpu",
        [{"metric": "lat_ms", "value": 12.0, "unit": "ms"}],
    )
    best = gates.best_prior(str(tmp_path), "cpu-fallback")
    assert best["lat_ms"][0] == 8.0 and best["lat_ms"][1] == "BENCH_r01.json"


def test_gates_missing_fresh_measurement_warns(tmp_path):
    _write_prior(
        tmp_path,
        "BENCH_r01.json",
        "cpu",
        [{"metric": "lat_ms", "value": 8.0, "unit": "ms"}],
    )
    fresh = [
        {"metric": "bench_platform", "value": "cpu"},
        {"metric": "lat_ms", "value": None, "note": "skipped: no budget"},
    ]
    (result,) = gates.gate_evidence(fresh, str(tmp_path))
    assert result.status == "warn" and "skipped" in result.note


def test_gates_parse_real_driver_artifact():
    """The repo's own BENCH_r05.json (driver wrapper schema) parses and
    classifies as cpu-fallback."""
    lines = gates.parse_artifact(str(REPO / "BENCH_r05.json"))
    assert gates.artifact_backend(lines) == "cpu-fallback"
    assert "happy_path_4v_height_latency" in gates.config_lines(lines)


def test_obs_report_cli_runs_against_repo(tmp_path):
    """scripts/obs_report.py end to end over a synthetic fresh artifact."""
    fresh = tmp_path / "bench_evidence.jsonl"
    fresh.write_text(
        "\n".join(
            json.dumps(line)
            for line in [
                {
                    "metric": "happy_path_4v_height_latency",
                    "config": "happy_path_4v_height_latency",
                    "value": 20.0,
                    "unit": "ms",
                    "backend": "cpu-fallback",
                    "probe": "ok",
                    "ts": 0,
                }
            ]
        )
    )
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "obs_report.py"),
            "--evidence",
            str(fresh),
            "--repo",
            str(REPO),
            "--fail-on",
            "never",
        ],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "happy_path_4v_height_latency" in proc.stdout
    assert "backend: cpu-fallback" in proc.stdout
