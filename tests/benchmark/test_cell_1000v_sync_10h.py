"""The cell ``ecdsa-1000v.sync-10h`` (PR 51): what it holds of
``BENCHMARK.json`` (one position-free ``declared(root)``), the real files
through ``run.load_cell``, the deployment and its traffic arithmetic, its two
readers with and without what they read, and a CPU rehearsal of the cell's
control flow at four validators (no number of which is a device number)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402  (benchmark/run.py)
from benchmark.drivers import sync as driver  # noqa: E402
from benchmark.lib import stage_reduce  # noqa: E402
from benchmark.lib.readers import read_metric  # noqa: E402
import contract_predicates as contract  # noqa: E402  (beside this file)
from test_benchmark_rehearsal import _run  # noqa: E402  (beside this file)

CELL = "ecdsa-1000v.sync-10h"
END_TO_END = ["setup_s", "sync_sigs_per_s"]
# Appended by PR 51 in this order, for this cell; each with its layer.
NEW_IN_PR_51 = {
    "sync_recover_membership_us_per_lane": "kernels",
    "sync_table_rows_per_dispatch": "dispatch and wait",
}
# Readers that say nothing of one cell, joined and not copied: the sync cells'
# (PR 45 joined the same), and the tally span's, which driver ``sync`` opens too.
JOINED = contract.JOINED_BY_PR_45 + ["pos_tally_ms_per_call"]
PER_LAYER = sorted(["compiles_in_window", "warm_s"] + JOINED + list(NEW_IN_PR_51))
# Driver ``sync`` gives this reader no count: the cell must not list it.
NOT_JOINED = "pos_table_uploads_per_call"


def cell_1000v(root: str = ROOT) -> None:
    """The cell's statement, true wherever a later PR puts its entries (for a
    ``benchmark`` PR to fold into ``contract_predicates.every_accepted_cell``)."""
    bench = contract.bench_of(root)
    contract.declares(
        bench, {"name": CELL, "config": "ecdsa-1000v", "traffic": "sync-10h", "chips": 1}
    )
    got = contract.selection(CELL, root)
    assert contract.in_order(END_TO_END, got["end_to_end"])
    assert contract.in_order(PER_LAYER, got["per_layer"])
    assert NOT_JOINED not in got["per_layer"]
    declared_ = {m["name"]: m for m in bench["per_layer"]}
    assert contract.in_order(list(NEW_IN_PR_51), list(declared_))
    for name, layer in NEW_IN_PR_51.items():
        assert CELL in declared_[name]["workloads"]
        assert declared_[name]["moves"] == "sync_sigs_per_s"
        assert declared_[name]["layer"] == layer
    for name in JOINED:
        assert CELL in declared_[name]["workloads"], name
    rate = next(m for m in bench["end_to_end"] if m["name"] == "sync_sigs_per_s")
    assert CELL in rate["workloads"]
    # The cells accepted before it get neither of its two.
    for other in (*contract.SELECTION_OF_PR_32, contract.NODE, contract.POS_SYNC):
        assert not set(contract.selection(other, root)["per_layer"]) & set(NEW_IN_PR_51), other


def declared(root: str = ROOT) -> None:
    """All this file holds of ``BENCHMARK.json``: the copy test of
    ``test_benchmark_contract.py`` finds this function by glob and calls it on
    a tree with a further cell in it."""
    cell_1000v(root)


@pytest.fixture(scope="module")
def cell():
    return bench_run.load_cell(CELL)


def test_the_cell_is_declared_and_breaks_no_accepted_cells_contract(cell):
    declared()
    contract.every_accepted_cell()  # with this cell in place
    why = cell["cell"]["why"]
    assert len(why) <= 200
    for said in ("10 blocks x 800 seals", "3 full 2,048-lane chunks", "1,856", "2,048-row table", "1 chip"):
        assert said in why, said
    # Fourteen readers joined, two brought, two that every cell reports.
    assert [m["name"] for _p, m in cell["per_layer"]] == PER_LAYER and len(PER_LAYER) == 18
    assert [m["name"] for _p, m in cell["end_to_end"]] == END_TO_END


def test_the_predicate_can_fail(tmp_path):
    """One of its two handed to an accepted cell, or its name taken off a
    reader it joined, is refused."""
    import json

    def written(bench):
        root = tmp_path / f"copy{len(os.listdir(tmp_path))}"
        os.makedirs(root)
        os.symlink(HERE, root / "benchmark")
        with open(root / "BENCHMARK.json", "w") as fh:
            json.dump(bench, fh)
        return str(root)

    cell_1000v(written(contract.bench_of()))
    handed = contract.bench_of()
    next(m for m in handed["per_layer"] if m["name"] == "sync_table_rows_per_dispatch")["workloads"].append("ecdsa-100v.sync")
    taken = contract.bench_of()
    next(m for m in taken["per_layer"] if m["name"] == "sync_lane_occupancy")["workloads"].remove(CELL)
    joined = contract.bench_of()
    next(m for m in joined["per_layer"] if m["name"] == NOT_JOINED)["workloads"].append(CELL)
    for broken in (handed, taken, joined):
        with pytest.raises(AssertionError):
            cell_1000v(written(broken))


def test_the_deployment_states_its_guarantees_and_cuts_nothing_but_the_peers(cell):
    cfg = cell["config"]
    entry = next(c for c in contract.bench_of()["configs"] if c["name"] == "ecdsa-1000v")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for cited in ("BASELINE.json configs[2]", "10 heights pipelined", "validator_manager.go:129-135", "core/ibft.go"):
        assert cited in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["peers"] and "pre-signed" in cfg["peers"]
    assert cfg["scheme"] == "ecdsa-secp256k1" and cfg["voting_power"] == "equal, 1 each"
    assert (cfg["validators"], cfg["quorum"], cfg["max_faulty"]) == (1000, 667, 333)
    assert cfg["quorum"] == 2 * cfg["validators"] // 3 + 1 and cfg["base_round_timeout_s"] == 10
    # Word for word the guarantees every library cell is held to.
    assert cfg["guarantees"] == bench_run.load_cell("ecdsa-100v.sync")["config"]["guarantees"]
    a = cfg["assumed"]
    assert cfg["quorum"] < a["seals_per_block"] == 800 < cfg["validators"]
    assert (a["message_delay_ms"], a["block_time_s"], a["heights_per_request"]) == (0, 0, 10)
    # The last table rung and no other: over 512, within 2,048.
    assert stage_reduce.TABLE_BUCKETS[-2] < cfg["validators"] <= stage_reduce.TABLE_BUCKETS[-1] == 2048


def test_the_traffic_adds_up(cell):
    cfg, mix = cell["config"], cell["traffic"]
    spb, quorum = cfg["assumed"]["seals_per_block"], cfg["quorum"]
    keys = set(bench_run.load_cell("ecdsa-100v.sync")["traffic"])
    assert set(mix) == keys and mix["driver"] == "sync" and mix["name"] == "sync-10h"
    assert mix["lanes_per_call"] == 8000 == 10 * spb == 3 * 2048 + 1856
    blocks = mix["lanes_per_call"] // spb
    assert blocks == cfg["assumed"]["heights_per_request"] == 10 and mix["ranges"] == 3
    starts = [1 + k * blocks for k in range(mix["ranges"])]
    assert starts == [1, 11, 21]
    # No chunk edge falls on a block's end: the third, sixth and eighth block
    # lie across two dispatches; and the tail is partly dead.
    edges = (2048, 4096, 6144)
    assert [next(i for i in range(1, blocks + 1) if spb * (i - 1) < e < spb * i) for e in edges] == [3, 6, 8]
    assert -(-mix["lanes_per_call"] // 2048) == 4 and 4 * 2048 - mix["lanes_per_call"] == 192
    assert round(100 * 8000 / 8192, 2) == 97.66 and "97.66%" in mix["lanes_rule"]
    assert "100% by construction" not in mix["lanes_rule"]
    # 30 mangled seals in every block whose height 8 divides, quorum kept.
    assert (mix["corrupt_every"], mix["corrupt_seals"]) == (8, 30)
    assert driver.window_corrupt_seals(mix, spb, quorum) == 30 and spb - 30 == 770 >= quorum
    for first in starts:
        assert len([h for h in range(first, first + blocks) if h % mix["corrupt_every"] == 0]) == 1
    # The check range: heights 31-32, 1,600 lanes in the loaded 2,048-lane
    # shape, height 32 corrupted; then height 32 cut to 666 valid seals.
    check_blocks = max(1, mix["check_lanes"] // spb)
    check_start = 1 + mix["ranges"] * blocks
    heights = list(range(check_start, check_start + check_blocks))
    assert heights == [31, 32] and check_blocks * spb == 1600 <= mix["check_lanes"] == 2048
    assert [h % mix["corrupt_every"] == 0 for h in heights] == [False, True]
    assert min(mix["corrupt_seals"], spb - quorum) == 30  # the check range's spec
    assert heights[len(heights) // 2] == 32 and quorum - 1 == 666
    assert (mix["warm_calls"], mix["trace_seconds"]) == (1, 1.0)
    # The lead-in and the warm dispatch: the first full chunk of range 0.
    assert -(-mix["check_lanes"] // spb) == 3 and 3 * spb >= mix["check_lanes"]


def _ctx(spans=(), counts=None):
    return {"spans": list(spans), "counts": dict(counts or {}), "ledger": None}


def test_both_new_readers_read_what_they_name_and_nothing_where_that_is_absent(cell, monkeypatch):
    files = {m["name"]: (path, m) for path, m in cell["per_layer"]}
    read = lambda name, ctx: read_metric(ctx, files[name][1], files[name][0])  # noqa: E731
    for name in NEW_IN_PR_51:
        assert not os.path.exists(files[name][0][: -len(".json")] + ".py")  # data files only
    dispatch = lambda **args: ("X", "verify.dispatch", "t", 0, 700, args)  # noqa: E731
    rung = "sync_table_rows_per_dispatch"
    change = _ctx(
        [dispatch(route="device", operands=6, ladder="pallas", table_rows=2048)] * 4,
        {"operations": 1},
    )
    assert read(rung, change) == 2048
    # The parent's program: the span is there, the argument is not.
    parents = _ctx([dispatch(route="device", operands=6, ladder="pallas")] * 4, {"operations": 1})
    assert read(rung, parents) is None
    assert read(rung, _ctx()) is None and read(rung, {"spans": None, "counts": {}}) is None

    # The stage reader: ``membership`` alone, which the accepted
    # ``sync_recover_address_us_per_lane`` folds into ``address``.
    alone = "sync_recover_membership_us_per_lane"
    assert files[alone][1]["read"] == {"kind": "reader", "module": "recover_stage", "stages": ["membership"]}
    split = {s: 0.0 for s in stage_reduce.STAGES}
    split.update({"recover.address": 0.071, "recover.membership": 0.048})
    monkeypatch.setattr(stage_reduce, "stages_of_run", lambda ctx: {"us_per_lane": split})
    assert read(alone, change) == 0.048
    assert read("sync_recover_address_us_per_lane", change) == pytest.approx(0.119)
    # A reduction that failed safe, a run with no device trace: left out.
    monkeypatch.setattr(stage_reduce, "stages_of_run", lambda ctx: {"us_per_lane": None, "unscoped_share": 100.0})
    assert read(alone, change) is None
    monkeypatch.undo()
    assert read(alone, dict(change, trace=None, ledger_traced=None)) is None
    assert read(alone, {"spans": None, "counts": {}}) is None


def test_rehearsal_reports_the_rate_and_every_comparison_beside_its_limit(monkeypatch):
    rc, lines = _run(monkeypatch, CELL, 0, 0.5, validators=4, lanes=32)
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sync_sigs_per_s", "setup_s"}
    assert result["metrics"]["sync_sigs_per_s"]["unit"] == "sigs/s"
    assert result["metrics"]["sync_sigs_per_s"]["value"] > 0
    assert list(result)[-1] == "compared" and set(result["compared"]) == {
        "operations_failed", "compiles_in_window", "guarantees_broken",
        "mask_lanes_off_the_labels", "oracle_lanes_off_the_labels", "sub_quorum_ranges_accepted",
    }
    assert all(c == {"value": 0, "limit": 0} for c in result["compared"].values())
    assert info["driver"] == "sync" and info["lanes_per_call"] == 32 and info["blocks_per_call"] == 8
    assert info["cutover"] == 16 and info["cutover_source"] == "default"
    assert "quorum" in info["sub_quorum_refused"]


def test_the_traced_rehearsal_reports_the_rung_beside_the_joined_readers(monkeypatch):
    rc, lines = _run(monkeypatch, CELL, 1, 1.0, validators=4, lanes=32)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert "sync_sigs_per_s" not in got and NOT_JOINED not in got
    # Four validators: the 8-row rung (the chip's cell reads 2,048).
    assert got["sync_table_rows_per_dispatch"] == 8
    assert result["metrics"]["sync_table_rows_per_dispatch"]["unit"] == "rows"
    assert got["sync_device_lane_share"] == 100.0 and got["sync_lane_occupancy"] == 100.0
    assert got["pos_tally_ms_per_call"] > 0 and got["compiles_in_window"] == 0
    assert {"sync_host_ms_per_call", "sync_pack_ms_per_chunk", "sync_device_wait_ms_per_chunk"} <= set(got)
