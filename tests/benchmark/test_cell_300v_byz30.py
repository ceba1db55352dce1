"""The cell ``ecdsa-300v.flood-byz30`` (PR 31): the real files through
``run.load_cell``, the metric list ISSUE 31 gives it (and the three its
review added), the traffic mix's arithmetic, its two reader modules, and a
CPU rehearsal of the cell's control
flow at ten validators (no number of which is a device number)."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402  (benchmark/run.py)
from benchmark.drivers import round0  # noqa: E402
from benchmark.lib.readers import read_metric  # noqa: E402
import contract_predicates as contract  # noqa: E402  (beside this file)
from test_benchmark_rehearsal import _run as rehearse  # noqa: E402  (beside this file)

CELL = contract.FLOOD_300V
SHARED_WITH_THE_100V_FLOOD = contract.SHARED_WITH_THE_100V_FLOOD
NEW_IN_PR_31 = contract.NEW_IN_PR_31


def declared(root: str = ROOT) -> None:
    """All this file holds of ``BENCHMARK.json``, wherever the entries stand
    (PR 50: found by glob and called on a copy with a further cell in it)."""
    contract.flood_300v(root)


@pytest.fixture(scope="module")
def cell():
    return bench_run.load_cell(CELL)


def test_the_cell_loads_with_the_metrics_the_issue_lists(cell):
    assert cell["cell"] == {
        "name": CELL,
        "config": "ecdsa-300v",
        "traffic": "flood-byz30",
        # One validator's verifier is one chip and nothing here exists only
        # across chips: the cell holds the whole four-chip host for a steady
        # host clock alone (on one chip the driver's check refused the spread
        # of finalize_p90_ms), and its `why` has to say so.
        "chips": 4,
        "why": cell["cell"]["why"],
    }
    assert "512 lanes" in cell["cell"]["why"] and len(cell["cell"]["why"]) <= 200
    assert "steadiness alone" in cell["cell"]["why"]
    # Declared with its configuration; selects, in order, the metrics ISSUE 31
    # gave it; the readers it brought are its own, but ``flushes_per_height``,
    # which another cell may join: wherever the entries stand (PR 43).
    declared()
    for _path, m in cell["per_layer"]:
        if m["name"] in NEW_IN_PR_31:
            assert "drivers" not in m and "workloads" not in m
            assert m["moves"] == "finalize_p50_ms"


def test_the_kernel_metrics_are_the_100v_readers_on_another_cell(cell):
    files = {m["name"]: m for _p, m in cell["per_layer"]}
    for name in NEW_IN_PR_31:
        if not name.startswith("flood300_"):
            continue
        with open(os.path.join(HERE, "layer_metrics", name[len("flood300_"):] + ".json")) as fh:
            old = json.load(fh)
        new = files[name]
        assert new["read"] == old["read"]  # same module, same stages or share
        for key in ("layer", "unit", "better", "source", "moves"):
            assert new[key] == old[key], (name, key)


def test_the_deployment_and_its_byzantine_arithmetic(cell):
    cfg, mix = cell["config"], cell["traffic"]
    n = cfg["validators"]
    assert (n, cfg["quorum"], cfg["max_faulty"]) == (300, 201, 99)
    assert cfg["quorum"] == 2 * n // 3 + 1 and cfg["max_faulty"] == (n - 1) // 3
    assert cfg["architecture"] is None and cfg["reduced"] == ["peers"]
    assert cfg["scheme"] == "ecdsa-secp256k1" and cfg["base_round_timeout_s"] == 10
    with open(os.path.join(HERE, "configs", "ecdsa-100v.json")) as fh:
        assert cfg["guarantees"] == json.load(fh)["guarantees"]  # word for word
    assert cfg["quorum"] <= cfg["assumed"]["seals_per_block"] <= 254
    assert len(cfg["source"]) <= 200 and "byzantine_test.go" in cfg["source"]

    assert (mix["driver"], mix["delay_ms"], mix["warm_heights"]) == ("round0", 0, 3)
    corrupt = round0.corrupted_peers(n, mix["corrupt_share"])
    assert corrupt == cfg["byzantine_peers_per_height"] == 90 <= cfg["max_faulty"]
    followers = n - 2  # the peers without the height's proposer
    bad_envelopes = corrupt // 2
    honest_prepares = followers - corrupt
    valid_envelope_commits = (n - 1) - bad_envelopes
    valid_seals = (n - 1) - corrupt + 1  # with the node's own
    assert (honest_prepares, valid_envelope_commits, valid_seals) == (208, 254, 210)
    # A PREPARE quorum is quorum - 1 beside the proposal; the node's own counts.
    assert honest_prepares >= cfg["quorum"] - 1 and valid_seals >= cfg["quorum"]
    assert valid_seals - cfg["quorum"] == 9
    # ISSUE 33's 13200 messages a second: a throw-away height, three warm
    # ones and 440 for the window, of which PR 32's program uses ~272.
    assert mix["presign_msgs_per_s"] == 13200
    assert round0.presigned_heights(mix, n, 20.0) == 1 + 3 + math.ceil(20 * 13200 / 600) == 444
    # Every flood of a height fits one 512-lane dispatch and no smaller bucket.
    from go_ibft_tpu.verify.batch import _BATCH_BUCKETS

    for lanes in (followers + 1, n, valid_envelope_commits + 1):
        assert next(b for b in _BATCH_BUCKETS if b >= lanes) == 512


def test_flushes_per_height_reads_the_span_and_returns_nothing_without_it(cell):
    path, metric = next(
        (p, m) for p, m in cell["per_layer"] if m["name"] == "flushes_per_height"
    )
    flush = ("X", "ingress.flush", "MainThread", 0, 10, {"lanes": 299, "cut": False})
    spans = [flush] * 29 + [
        ("X", "ingress.batch", "node-0", 0, 9, {"lanes": 299}),
        ("i", "ingress.flush", "MainThread", 0, 0, None),
    ]
    ctx = {"spans": spans, "counts": {"operations": 10}}
    assert read_metric(ctx, metric, path) == pytest.approx(2.9)
    # The parent's program has no such span: nothing to read, and no raise.
    assert read_metric({"spans": spans[29:], "counts": {"operations": 10}}, metric, path) is None
    assert read_metric({"spans": None, "counts": {}}, metric, path) is None
    assert read_metric({"spans": [flush], "counts": {"operations": 0}}, metric, path) is None


def test_rejected_lane_share_reads_the_verdicts_and_returns_nothing_without_them(cell):
    path, metric = next(
        (p, m) for p, m in cell["per_layer"] if m["name"] == "rejected_lane_share"
    )

    def verdicts(kind, live, rejected):
        args = {"kind": kind, "route": "device", "live": live, "rejected": rejected}
        return ("i", "verify.verdicts", "node-0", 0, 0, args)

    # One height by the mix's labels: 90 + 45 bad envelopes, 45 bad seals.
    height = [
        verdicts("senders", 1, 0),
        verdicts("senders", 299, 90),
        verdicts("senders", 300, 45),
        verdicts("seals", 300, 45),  # every COMMIT's seal, the 45 behind a bad envelope too (PR 32)
    ]
    other = [("X", "verify.drain", "node-0", 0, 9, {"kind": "senders", "route": "device"})]
    got = read_metric({"spans": height * 3 + other, "counts": {}}, metric, path)
    assert got == pytest.approx(100 * 180 / 900)
    assert "20.0%" in metric["what"] and "180 of 900" in metric["what"] and round(got, 2) == 20.0
    # The parent's program has no such instant: nothing to read, and no raise.
    assert read_metric({"spans": other, "counts": {}}, metric, path) is None
    assert read_metric({"spans": None, "counts": {}}, metric, path) is None


def test_rehearsal_at_ten_validators_reports_the_cells_host_side_metrics(monkeypatch):
    """``run.run`` on the CPU with the committee cut to ten (three corrupted
    a height): the traced line carries every metric of the cell that needs
    neither the device route nor a device trace, ``flushes_per_height``
    among them."""
    rc, lines = rehearse(monkeypatch, CELL, 1, 1.0, validators=10, presign_msgs_per_s=6000)
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert info["corrupted_peers_per_height"] == 3 and info["ran_out_of_presigned"] is False
    got = result["metrics"]
    # Ten validators are under the cutover, so the host route serves and the
    # device route's spans and ledger rows are not there to read.
    device_route = {"device_wait_ms_per_drain", "lane_occupancy", "pack_ms_per_drain"}
    host_side = set(SHARED_WITH_THE_100V_FLOOD) - device_route | {
        "flushes_per_height",
        "rejected_lane_share",
    }
    assert host_side <= set(got), host_side - set(got)
    assert got["device_lane_share"]["value"] == 0.0
    # One height in ten is the node's own proposal: two flushes, else three.
    assert 2.5 <= got["flushes_per_height"]["value"] <= 3.0
    assert got["msgs_per_flush"]["value"] == 9  # median of 1, 9, 10
    # 3 + 1 bad envelopes of 1 + 9 + 10 and at most 2 bad seals of <= 9: the
    # host early exit stops at quorum, in arrival order.
    assert 100 * 4 / 29 <= got["rejected_lane_share"]["value"] <= 100 * 6 / 27
    assert got["compiles_in_window"]["value"] == 0


def test_a_verifier_that_passes_every_envelope_makes_the_flood_incorrect(monkeypatch):
    """The timed path broken underneath: an answer altered where it is
    produced.  The rest of a run is driven as it is, and ``correct`` comes
    out false by the labels the heights carry."""
    import numpy as np

    from go_ibft_tpu.verify import AdaptiveBatchVerifier

    monkeypatch.setattr(
        AdaptiveBatchVerifier, "verify_senders", lambda self, msgs: np.ones(len(msgs), dtype=bool)
    )
    rc, lines = rehearse(monkeypatch, CELL, 0, 0.5, validators=10)
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is False
    # Whichever the labels catch first: a corrupted peer's seal finalized, or
    # its PREPARE in the locked quorum.
    assert "corrupted" in info["guarantee_broken"]
    assert result["compared"]["heights_against_their_labels"] == {"value": 1, "limit": 0}
    assert result["compared"]["guarantees_broken"] == {"value": 1, "limit": 0}
