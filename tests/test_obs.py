"""Observability subsystem: the flight recorder and its export.

Pins the ISSUE 4 contracts:

* the span API is thread-safe, ring-bounded, and exports valid Chrome
  ``trace_event`` JSON with one track per node;
* disabled-mode tracing is a single predicate check (a no-op context
  manager — no recorder, no clock reads).
"""

import json
import pathlib
import re
import sys
import threading
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from go_ibft_tpu.obs import export, trace
from go_ibft_tpu.obs.recorder import RingRecorder


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
# span names: the code and docs/OBSERVABILITY.md's taxonomy, both ways
# ---------------------------------------------------------------------------

_REPO = pathlib.Path(__file__).resolve().parent.parent
_SITE = re.compile(r'trace\.(?:span|instant|begin)\(\s*"([^"]+)"(\s*\+)?')


def _names_in_the_code():
    """Literal names at ``trace.span`` / ``instant`` / ``begin`` sites of the
    package; a site that builds its name (``"chaos." + kind``) is a prefix."""
    names, prefixes = set(), set()
    for path in (_REPO / "go_ibft_tpu").rglob("*.py"):
        for name, plus in _SITE.findall(path.read_text()):
            (prefixes if plus else names).add(name)
    return names, prefixes


def _names_in_the_taxonomy():
    """Every backticked name of the first column of the "Span taxonomy"
    table; ``chaos.*`` is a prefix."""
    text = (_REPO / "docs" / "OBSERVABILITY.md").read_text()
    table = text.split("### Span taxonomy", 1)[1].split("\n\n", 2)[1]
    names, prefixes = set(), set()
    for row in table.splitlines()[2:]:
        first = row.split("|")[1]
        for name in re.findall(r"`([^`]+)`", first):
            if name.endswith(".*"):
                prefixes.add(name[:-1])
            else:
                names.add(name)
    return names, prefixes


def test_every_span_name_in_the_code_is_in_the_taxonomy():
    code, built = _names_in_the_code()
    documented, prefixes = _names_in_the_taxonomy()
    assert len(code) > 60  # the pattern still finds the sites
    missing = {
        n for n in code - documented if not any(n.startswith(p) for p in prefixes)
    }
    assert not missing, f"span names with no taxonomy row: {sorted(missing)}"
    assert built <= prefixes, f"built names with no `x.*` row: {built - prefixes}"


def test_every_taxonomy_row_names_a_span_the_code_emits():
    code, built = _names_in_the_code()
    documented, prefixes = _names_in_the_taxonomy()
    stale = documented - code
    assert not stale, f"taxonomy rows for names nothing emits: {sorted(stale)}"
    assert prefixes <= built


@pytest.mark.parametrize(
    "name",
    ["ingress.wait", "engine.wake", "engine.store", "engine.finalize", "engine.send"],
)
def test_the_seam_spans_of_issue_37_are_emitted_and_documented(name):
    code, _ = _names_in_the_code()
    documented, _ = _names_in_the_taxonomy()
    assert name in code and name in documented
