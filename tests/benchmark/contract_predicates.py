"""What the accepted cells' contract tests hold of ``BENCHMARK.json``, as
statements that are true wherever a later PR appends its entries (PR 43).

Each predicate takes the ``root`` of a checkout (``BENCHMARK.json`` and
``benchmark/`` under it) and raises ``AssertionError``; the cells' test files
call them on the repo, and ``test_benchmark_contract.py`` calls all of them on
a copy with a further cell appended, flood side and sync side, and on one with
it put before an accepted cell's entries (the node's, the PoS cell's).
A cell's statement: it and its configuration ARE declared; it still selects,
in order, every metric it selected (an ordered subset: a later PR may give an
accepted cell one more reader); a reader it shares still CONTAINS it; the
metrics it brought as its own are still its own.  Nothing here asks where in
a list an entry stands, or what else a list holds (PR 50: the PoS cell's
statement too, which PR 45 had written with pins in its own test file).

A cell's own test file says what it holds of ``BENCHMARK.json`` through ONE
function, ``declared(root=ROOT)``, that calls the cell's predicate here: the
copy test finds every ``test_cell_*.py`` by glob and calls it on the copy, so
a later cell's file is held to the rule by a test it cannot edit.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402  (benchmark/run.py)

FLOOD_300V = "ecdsa-300v.flood-byz30"
NODE = "ecdsa-100v-node.grpc-flood"

# What each cell reported on PR 32's tree, where a metric file's ``drivers``
# and ``workloads`` keys decided it: PR 33 made ``BENCHMARK.json`` the one
# authority, and the four cells select what they did, in the same order.
SELECTION_OF_PR_32 = {
    "ecdsa-100v.flood": {
        "end_to_end": ["finalize_p50_ms", "finalize_p90_ms", "setup_s"],
        "per_layer": [
            "compiles_in_window",
            "device_lane_share",
            "device_wait_ms_per_drain",
            "dispatches_per_height",
            "engine_self_ms",
            "lane_occupancy",
            "msgs_per_flush",
            "pack_ms_per_drain",
            "recover_address_us_per_lane",
            "recover_affine_us_per_lane",
            "recover_ladder_us_per_lane",
            "recover_layout_share",
            "recover_pre_ladder_us_per_lane",
            "recover_unscoped_share",
            "recover_us_per_lane",
            "warm_s",
        ],
    },
    "ecdsa-100v.sync": {
        "end_to_end": ["setup_s", "sync_sigs_per_s"],
        "per_layer": [
            "compiles_in_window",
            "ecdsa_recover_roofline",
            "sync_device_lane_share",
            "sync_device_wait_ms_per_chunk",
            "sync_host_ms_per_call",
            "sync_lane_occupancy",
            "sync_pack_ms_per_chunk",
            "sync_recover_address_us_per_lane",
            "sync_recover_affine_us_per_lane",
            "sync_recover_ladder_us_per_lane",
            "sync_recover_layout_share",
            "sync_recover_pre_ladder_us_per_lane",
            "sync_recover_unscoped_share",
            "sync_recover_us_per_lane",
            "warm_s",
        ],
    },
    "ecdsa-300v.flood-byz30": {
        "end_to_end": ["finalize_p50_ms", "finalize_p90_ms", "setup_s"],
        "per_layer": [
            "compiles_in_window",
            "device_lane_share",
            "device_wait_ms_per_drain",
            "dispatches_per_height",
            "engine_self_ms",
            "flood300_recover_address_us_per_lane",
            "flood300_recover_affine_us_per_lane",
            "flood300_recover_ladder_us_per_lane",
            "flood300_recover_layout_share",
            "flood300_recover_pre_ladder_us_per_lane",
            "flood300_recover_unscoped_share",
            "flood300_recover_us_per_lane",
            "flushes_per_height",
            "lane_occupancy",
            "msgs_per_flush",
            "pack_ms_per_drain",
            "rejected_lane_share",
            "warm_s",
        ],
    },
}
SELECTION_OF_PR_32["ecdsa-4v.sync"] = SELECTION_OF_PR_32["ecdsa-100v.sync"]

# The 300-validator cell (PR 31).
FLOOD_300V_END_TO_END = ["finalize_p50_ms", "finalize_p90_ms", "setup_s"]
SHARED_WITH_THE_100V_FLOOD = [
    "compiles_in_window",
    "device_lane_share",
    "device_wait_ms_per_drain",
    "dispatches_per_height",
    "engine_self_ms",
    "lane_occupancy",
    "msgs_per_flush",
    "pack_ms_per_drain",
    "warm_s",
]
NEW_IN_PR_31 = [
    "flood300_recover_address_us_per_lane",
    "flood300_recover_affine_us_per_lane",
    "flood300_recover_ladder_us_per_lane",
    "flood300_recover_layout_share",
    "flood300_recover_pre_ladder_us_per_lane",
    "flood300_recover_unscoped_share",
    "flood300_recover_us_per_lane",
    "flushes_per_height",
    "rejected_lane_share",
]
# Readers of PR 31 that say nothing of one cell: another cell may join their list.
SHARED_READERS_OF_PR_31 = ["flushes_per_height"]

# The node cell (PR 39).
NODE_END_TO_END = ["finalize_p50_ms", "finalize_p90_ms", "setup_s"]
NODE_PER_LAYER = [
    "compiles_in_window",
    "device_lane_share",
    "dispatches_per_height",
    "lane_occupancy",
    "node_flushes_per_height",
    "node_lanes_per_sched_dispatch",
    "node_msgs_per_flush",
    "node_multicast_ms_per_height",
    "node_recover_us_per_lane",
    "node_sched_dispatches_per_height",
    "node_sched_queue_ms_per_height",
    "node_wal_fsync_ms_per_height",
    "warm_s",
]
NEW_IN_PR_39 = [name for name in NODE_PER_LAYER if name.startswith("node_")]
NODE_ENTRIES_AS_PR_39_APPENDED_THEM = [
    "node_msgs_per_flush",
    "node_sched_dispatches_per_height",
    "node_lanes_per_sched_dispatch",
    "node_sched_queue_ms_per_height",
    "node_wal_fsync_ms_per_height",
    "node_multicast_ms_per_height",
    "node_recover_us_per_lane",
    "node_flushes_per_height",
]
NODE_LAYERS = {
    "node_msgs_per_flush": "ingress",
    "node_flushes_per_height": "ingress",
    "node_multicast_ms_per_height": "ingress",
    "node_sched_dispatches_per_height": "verifier policy",
    "node_lanes_per_sched_dispatch": "verifier policy",
    "node_sched_queue_ms_per_height": "engine",
    "node_wal_fsync_ms_per_height": "storage",
    "node_recover_us_per_lane": "kernels",
}
LIBRARY_CELLS = ("ecdsa-100v.flood", "ecdsa-300v.flood-byz30", "ecdsa-100v.sync")

# The PoS sync cell (PR 45).
POS_SYNC = "ecdsa-100v-pos.sync-epochs"
POS_SYNC_END_TO_END = ["setup_s", "sync_sigs_per_s"]
POS_ENTRIES_AS_PR_45_APPENDED_THEM = [
    "pos_drains_per_call",
    "pos_sets_per_call",
    "pos_table_uploads_per_call",
    "pos_out_of_set_lane_share",
    "pos_tally_ms_per_call",
]
NEW_IN_PR_45 = sorted(POS_ENTRIES_AS_PR_45_APPENDED_THEM)
POS_LAYERS = {
    "pos_drains_per_call": "engine",
    "pos_sets_per_call": "engine",
    "pos_tally_ms_per_call": "engine",
    "pos_table_uploads_per_call": "dispatch and wait",
    "pos_out_of_set_lane_share": "verifier policy",
}
# The sync readers it joined, not copied: each says nothing of one cell.
JOINED_BY_PR_45 = [
    name for name in SELECTION_OF_PR_32["ecdsa-100v.sync"]["per_layer"]
    if name == "ecdsa_recover_roofline" or name.startswith("sync_")
]
POS_SYNC_PER_LAYER = sorted(["compiles_in_window", "warm_s"] + JOINED_BY_PR_45 + NEW_IN_PR_45)


def bench_of(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def selection(cell: str, root: str = ROOT) -> dict:
    """The metric names ``load_cell`` gives ``cell``, by group, in its order."""
    got = bench_run.load_cell(cell, root)
    return {
        group: [m["name"] for _p, m in got[group]]
        for group in ("end_to_end", "per_layer")
    }


def in_order(wanted: list, among: list) -> bool:
    """Whether every name of ``wanted`` is in ``among``, in ``wanted``'s order."""
    rest = iter(among)
    return all(name in rest for name in wanted)


def declares(bench: dict, cell: dict) -> None:
    """The cell is declared as ``cell`` says (its ``why`` aside) beside the
    configuration it names, wherever in their lists."""
    found = [w for w in bench["workloads"] if w["name"] == cell["name"]]
    assert len(found) == 1, cell["name"]
    assert {k: v for k, v in found[0].items() if k != "why"} == cell
    assert [c["name"] for c in bench["configs"]].count(cell["config"]) == 1


def pr_32_selection(cell: str, root: str = ROOT) -> None:
    """One of the four cells of PR 32 selects, in order, what it selected."""
    got = selection(cell, root)
    for group, names in SELECTION_OF_PR_32[cell].items():
        assert in_order(names, got[group]), (cell, group, got[group])


def flood_300v(root: str = ROOT) -> None:
    bench = bench_of(root)
    declares(
        bench,
        {"name": FLOOD_300V, "config": "ecdsa-300v", "traffic": "flood-byz30", "chips": 4},
    )
    got = selection(FLOOD_300V, root)
    assert in_order(FLOOD_300V_END_TO_END, got["end_to_end"])
    assert in_order(sorted(SHARED_WITH_THE_100V_FLOOD + NEW_IN_PR_31), got["per_layer"])
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_IN_PR_31:
        # BENCHMARK.json alone says which cell reports it (PR 33).
        if name in SHARED_READERS_OF_PR_31:
            assert FLOOD_300V in declared[name]["workloads"]
        else:
            assert declared[name]["workloads"] == [FLOOD_300V]
    # The old flood keeps its own kernel metrics and gets none of the new.
    old = selection("ecdsa-100v.flood", root)["per_layer"]
    assert not set(old) & set(NEW_IN_PR_31)
    assert "recover_us_per_lane" in old and "recover_us_per_lane" not in got["per_layer"]


def node(root: str = ROOT) -> None:
    bench = bench_of(root)
    declares(
        bench,
        # One chip does the work; the cell holds a four-chip host for a steady
        # host clock alone since PR 43 (on one chip its p50 spread by 2.9%).
        {"name": NODE, "config": "ecdsa-100v-node", "traffic": "grpc-flood", "chips": 4},
    )
    got = selection(NODE, root)
    assert in_order(NODE_END_TO_END, got["end_to_end"])
    assert in_order(NODE_PER_LAYER, got["per_layer"])
    declared = {m["name"]: m for m in bench["per_layer"]}
    # Its own metrics: declared, in the order PR 39 appended them, and its alone.
    assert in_order(NODE_ENTRIES_AS_PR_39_APPENDED_THEM, list(declared))
    for name in NEW_IN_PR_39:
        assert declared[name]["workloads"] == [NODE]
        assert declared[name]["moves"] == "finalize_p50_ms"
        assert declared[name]["layer"] == NODE_LAYERS[name]
    # The flood metrics it takes as they are still list it.
    for name in ("device_lane_share", "lane_occupancy", "dispatches_per_height"):
        assert NODE in declared[name]["workloads"]
    # ``flushes_per_height`` is the 300-validator cell's reader still; this
    # cell reads the same module as ``node_flushes_per_height``.
    assert FLOOD_300V in declared["flushes_per_height"]["workloads"]
    # The library cells get none of the node's metrics.
    for other in LIBRARY_CELLS:
        assert not set(selection(other, root)["per_layer"]) & set(NEW_IN_PR_39)
    # Four chips cost four times the chip time in every later check: these two
    # hold four, for steadiness alone (a later cell that exists only across
    # chips may hold four beside them: the contract's half counts, not this).
    assert {FLOOD_300V, NODE} <= {w["name"] for w in bench["workloads"] if w["chips"] == 4}


def pos_sync(root: str = ROOT) -> None:
    bench = bench_of(root)
    declares(
        bench,
        {"name": POS_SYNC, "config": "ecdsa-100v-pos", "traffic": "sync-epochs", "chips": 1},
    )
    got = selection(POS_SYNC, root)
    assert in_order(POS_SYNC_END_TO_END, got["end_to_end"])
    assert in_order(POS_SYNC_PER_LAYER, got["per_layer"])
    declared = {m["name"]: m for m in bench["per_layer"]}
    # Its own metrics: declared, in the order PR 45 appended them, for the sync
    # rate.  Each lists this cell; a later sync cell may join one whose reader
    # says nothing of one cell (``pos_tally_ms_per_call``, ``pos_table_uploads_per_call``).
    assert in_order(POS_ENTRIES_AS_PR_45_APPENDED_THEM, list(declared))
    for name in NEW_IN_PR_45:
        assert POS_SYNC in declared[name]["workloads"]
        assert declared[name]["moves"] == "sync_sigs_per_s"
        assert declared[name]["layer"] == POS_LAYERS[name]
    # The sync readers it joined, and the rate, still list it.
    for name in JOINED_BY_PR_45:
        assert POS_SYNC in declared[name]["workloads"]
    rate = next(m for m in bench["end_to_end"] if m["name"] == "sync_sigs_per_s")
    assert POS_SYNC in rate["workloads"]
    # The cells accepted before it get none of its metrics.
    for other in (*SELECTION_OF_PR_32, NODE):
        assert not set(selection(other, root)["per_layer"]) & set(NEW_IN_PR_45), other


def every_accepted_cell(root: str = ROOT) -> None:
    """Every predicate above, on the checkout at ``root``."""
    for cell in SELECTION_OF_PR_32:
        pr_32_selection(cell, root)
    flood_300v(root)
    node(root)
    pos_sync(root)
