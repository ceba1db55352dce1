"""The cell ``ecdsa-16chains.sync-storm`` (PR 54): what it holds of
``BENCHMARK.json`` (one position-free ``declared(root)``), the real files
through ``run.load_cell``, the deployment and its traffic arithmetic, its
readers with and without what they read, and CPU rehearsals of the cell's
control flow at four small chains (no number of which is a device number)."""

import contextlib
import gc
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402  (benchmark/run.py)
from benchmark.lib import multichain  # noqa: E402
from benchmark.lib.readers import read_metric  # noqa: E402
import contract_predicates as contract  # noqa: E402  (beside this file)

CELL = "ecdsa-16chains.sync-storm"
END_TO_END = ["setup_s", "sync_sigs_per_s"]
# Appended by PR 54 in this order, for this cell alone; each with its layer.
NEW_IN_PR_54 = {
    "storm_tenants_per_flush": "verifier policy",
    "storm_lanes_per_sched_dispatch": "verifier policy",
    "storm_sched_dispatches_per_storm": "verifier policy",
    "storm_sched_queue_ms_per_storm": "engine",
    "storm_flush_host_ms": "verifier policy",
    "storm_shed_lane_share": "verifier policy",
    "storm_quiet_tenant_ms": "engine",
}
# The sync readers that give a value on the dispatcher's drain, joined and not
# copied (each says nothing of one cell); one lane width is loaded, so the
# stage readers too.
JOINED = [
    name for name in contract.JOINED_BY_PR_45 if name != "sync_host_ms_per_call"
] + ["sync_recover_membership_us_per_lane", "sync_table_rows_per_dispatch"]
# A call's wall less its ``verify.drain`` spans: the dispatcher has no drain
# span and sixteen calls overlap, so it would read a wait as work.
NOT_JOINED = ["sync_host_ms_per_call", "pos_tally_ms_per_call", "pos_table_uploads_per_call"]
PER_LAYER = sorted(["compiles_in_window", "warm_s"] + JOINED + list(NEW_IN_PR_54))
ACCEPTED_BEFORE = (*contract.SELECTION_OF_PR_32, contract.NODE, contract.POS_SYNC, "ecdsa-1000v.sync-10h")


def cell_16chains(root: str = ROOT) -> None:
    """The cell's statement, true wherever a later PR puts its entries (for a
    ``benchmark`` PR to fold into ``contract_predicates.every_accepted_cell``)."""
    bench = contract.bench_of(root)
    contract.declares(
        # One chip does the work; the cell holds a four-chip host for a steady
        # host clock alone (on one chip its rate spread 3.3% and 12.6% by the
        # driver's measure in PR 54's two sets of six: PERF.md section 2).
        bench, {"name": CELL, "config": "ecdsa-16chains", "traffic": "sync-storm", "chips": 4}
    )
    got = contract.selection(CELL, root)
    assert contract.in_order(END_TO_END, got["end_to_end"])
    assert contract.in_order(PER_LAYER, got["per_layer"])
    assert not set(NOT_JOINED) & set(got["per_layer"])
    declared_ = {m["name"]: m for m in bench["per_layer"]}
    assert contract.in_order(list(NEW_IN_PR_54), list(declared_))
    for name, layer in NEW_IN_PR_54.items():
        assert CELL in declared_[name]["workloads"]
        assert declared_[name]["moves"] == "sync_sigs_per_s"
        assert declared_[name]["layer"] == layer
    for name in JOINED:
        assert CELL in declared_[name]["workloads"], name
    rate = next(m for m in bench["end_to_end"] if m["name"] == "sync_sigs_per_s")
    assert CELL in rate["workloads"]
    # The cells accepted before it get none of its seven.
    for other in ACCEPTED_BEFORE:
        assert not set(contract.selection(other, root)["per_layer"]) & set(NEW_IN_PR_54), other


def declared(root: str = ROOT) -> None:
    """All this file holds of ``BENCHMARK.json``: the copy test of
    ``test_benchmark_contract.py`` finds this function by glob and calls it on
    a tree with a further cell in it."""
    cell_16chains(root)


@pytest.fixture(scope="module")
def cell():
    return bench_run.load_cell(CELL)


def test_the_cell_is_declared_and_breaks_no_accepted_cells_contract(cell):
    declared()
    contract.every_accepted_cell()  # with this cell in place
    why = cell["cell"]["why"]
    assert len(why) <= 200
    for said in ("16 tenants", "64 blocks", "16,896", "TenantScheduler", "2,048-lane", "512-row", "4 chips for steadiness alone"):
        assert said in why, said
    assert [m["name"] for _p, m in cell["per_layer"]] == PER_LAYER and len(PER_LAYER) == 23
    assert [m["name"] for _p, m in cell["end_to_end"]] == END_TO_END


def test_the_predicate_can_fail(tmp_path):
    """One of its seven handed to an accepted cell, its name taken off a
    reader it joined, or the drain reader it must not join, is refused."""

    def written(bench):
        root = tmp_path / f"copy{len(os.listdir(tmp_path))}"
        os.makedirs(root)
        os.symlink(HERE, root / "benchmark")
        with open(root / "BENCHMARK.json", "w") as fh:
            json.dump(bench, fh)
        return str(root)

    def entry(bench, name):
        return next(m for m in bench["per_layer"] if m["name"] == name)

    cell_16chains(written(contract.bench_of()))
    handed = contract.bench_of()
    entry(handed, "storm_tenants_per_flush")["workloads"].append("ecdsa-100v.sync")
    taken = contract.bench_of()
    entry(taken, "sync_lane_occupancy")["workloads"].remove(CELL)
    joined = contract.bench_of()
    entry(joined, "sync_host_ms_per_call")["workloads"].append(CELL)
    for broken in (handed, taken, joined):
        with pytest.raises(AssertionError):
            cell_16chains(written(broken))


def test_the_deployment_states_its_guarantees_and_cuts_nothing_but_the_peers(cell):
    cfg = cell["config"]
    entry = next(c for c in contract.bench_of()["configs"] if c["name"] == "ecdsa-16chains")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for cited in ("BASELINE.json north_star", "docs/TENANCY.md", "many chains, one device", "4, 6, 30, 100"):
        assert cited in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["peers"] and "pre-signed" in cfg["peers"]
    assert cfg["scheme"] == "ecdsa-secp256k1" and cfg["base_round_timeout_s"] == 10
    classes = [(c["validators"], c["count"], c["quorum"]) for c in cfg["chains"]]
    assert classes == [(100, 2, 67), (30, 2, 21), (6, 4, 5), (4, 8, 3)]
    for c in cfg["chains"]:
        assert c["quorum"] == 2 * c["validators"] // 3 + 1
        assert c["max_faulty"] == (c["validators"] - 1) // 3
    sizes = multichain.sizes_of(cfg)
    assert len(sizes) == 16 and sum(sizes) == cfg["validators"] == 316
    # The 512-row table rung and no other: over 128, within 512.
    assert 128 < cfg["validators"] <= 512 and "NOT a quorum" in cfg["quorum_why"]
    # The scheduler's defaults, stated (sched/scheduler.py TenantScheduler.__init__).
    knobs = {k: v for k, v in cfg["sched"].items() if k != "why"}
    assert knobs == {
        "route": "auto", "window_s": 0.002, "quantum_lanes": 256,
        "max_queue_lanes": 8192, "max_dispatch_lanes": 2048,
    }
    g = cfg["guarantees"]
    assert {"finality", "sync", "isolation", "no_shed", "durability", "correct_means"} == set(g)
    library = bench_run.load_cell("ecdsa-1000v.sync-10h")["config"]["guarantees"]
    for key in ("finality", "sync"):  # as the library cells state them, PER CHAIN
        assert g[key] == "PER CHAIN: " + library[key][0].lower() + library[key][1:]
    for said in ("only from that chain's validator set", "whatever else shares its dispatch"):
        assert said in g["isolation"]
    for said in ("reference", "labels", "shed lane", "flush fault", "compile request"):
        assert said in g["correct_means"], said
    a = cfg["assumed"]
    assert a["seals_per_block"] == {"100": 80, "30": 24, "6": 6, "4": 4}
    assert (a["blocks_behind"], a["block_time_s"], a["outage_s"], a["message_delay_ms"]) == (64, 2, 128, 0)
    for c in cfg["chains"]:
        assert c["quorum"] <= a["seals_per_block"][str(c["validators"])] <= c["validators"]


def test_the_traffic_adds_up(cell):
    cfg, mix = cell["config"], cell["traffic"]
    assert mix["driver"] == "sync_storm" and mix["name"] == "sync-storm"
    assert (mix["tenants"], mix["blocks_per_call"], mix["ranges"]) == (16, 64, 3)
    assert mix["blocks_per_call"] == cfg["assumed"]["blocks_behind"]
    sizes = multichain.sizes_of(cfg)
    spb = cfg["assumed"]["seals_per_block"]
    per_tenant = [mix["blocks_per_call"] * spb[str(n)] for n in sizes]
    assert sorted(set(per_tenant), reverse=True) == [5120, 1536, 384, 256]
    assert sum(per_tenant) == mix["lanes_per_storm"] == 16896 == 2 * 5120 + 2 * 1536 + 4 * 384 + 8 * 256
    # Two chains carry 61% of the lanes, four 79%: a quarter of the tenants.
    assert round(100 * 2 * 5120 / 16896) == 61 and round(100 * (2 * 5120 + 2 * 1536) / 16896) == 79
    # Requests as the handle cuts a call at the dispatch cap; every tenant's
    # whole call is queued at once and stays under its queue cap.
    cap, queue = cfg["sched"]["max_dispatch_lanes"], cfg["sched"]["max_queue_lanes"]
    requests = [[min(cap, lanes - at) for at in range(0, lanes, cap)] for lanes in per_tenant]
    assert requests[0] == [2048, 2048, 1024] and sum(len(r) for r in requests) == 20
    assert max(per_tenant) == 5120 <= queue and 2 * 5120 > queue  # 128 blocks would shed
    # Every corrupt block keeps its chain's quorum; the refused lanes a storm.
    quorum = {str(c["validators"]): c["quorum"] for c in cfg["chains"]}
    assert mix["corrupt_every"] == 8 and mix["corrupt_seals"] == {"100": 3, "30": 2, "6": 1, "4": 1}
    for n, bad in mix["corrupt_seals"].items():
        assert spb[n] - bad >= quorum[n], n
    assert [spb[n] - mix["corrupt_seals"][n] for n in ("100", "30", "6", "4")] == [77, 22, 5, 3]
    assert mix["cross_chain_seals"] == {"100": 1}
    per_chain_blocks = mix["blocks_per_call"] // mix["corrupt_every"]
    refused = sum(per_chain_blocks * mix["corrupt_seals"][str(n)] for n in sizes)
    assert refused == 176 and "176 refused" in mix["corrupt_rule"]
    assert sum(per_chain_blocks * mix["cross_chain_seals"].get(str(n), 0) for n in sizes) == 16
    # Disjoint heights: no two chains share a height, so none a proposal hash;
    # every range of 64 holds eight multiples of 8.
    stride = mix["height_stride"]
    spans = [
        (stride * (k + 1) + 1, stride * (k + 1) + (mix["ranges"] + 1) * mix["blocks_per_call"])
        for k in range(16)
    ]
    assert all(hi < nxt for (_lo, hi), (nxt, _hi) in zip(spans, spans[1:]))
    for lo, _hi in spans:
        for r in range(mix["ranges"] + 1):
            first = lo + r * 64
            assert len([h for h in range(first, first + 64) if h % 8 == 0]) == 8
    assert (mix["warm_calls"], mix["trace_seconds"]) == (1, 1.0)


def _span(name, dur_us=1000, **args):
    return ("X", name, "t", 0, dur_us, args)


def test_the_storm_readers_read_what_they_name_and_nothing_where_that_is_absent(cell):
    files = {m["name"]: (path, m) for path, m in cell["per_layer"]}
    read = lambda name, ctx: read_metric(ctx, files[name][1], files[name][0])  # noqa: E731
    flush = [
        _span("sched.queue", 4000, kind="seals", lanes=2048),
        _span("sched.queue", 6000, kind="seals", lanes=256),
        _span("sched.coalesce", 12000, tenants=2, requests=2, lanes=2304),
        _span("sched.dispatch", 10000, route="device", lanes=2304),
        _span("verify.pack", 2500, kind="seal_lanes", lanes=2048, table_rows=512),
        _span("verify.dispatch", 700, route="device", table_rows=512),
        _span("verify.device_wait", 7000, route="device"),
        _span("sched.complete", 1500, requests=2, lanes=2304),
    ]
    counts = {"operations": 2, "live_lanes": 33792, "shed_lanes": 0, "quiet_tenant_ms": [90.0, 110.0, 100.0]}
    ctx = {"spans": flush * 4, "counts": counts, "ledger": None}
    assert read("storm_tenants_per_flush", ctx) == 2
    assert read("storm_lanes_per_sched_dispatch", ctx) == 2304
    assert read("storm_sched_dispatches_per_storm", ctx) == 2.0
    assert read("storm_sched_queue_ms_per_storm", ctx) == 20.0
    assert read("storm_flush_host_ms", ctx) == 4.0  # 2.5 pack + 1.5 complete a flush
    assert read("storm_shed_lane_share", ctx) == 0.0
    assert read("storm_quiet_tenant_ms", ctx) == 100.0
    assert read("sync_table_rows_per_dispatch", ctx) == 512
    assert read("sync_pack_ms_per_chunk", ctx) == 2.5 and read("sync_device_wait_ms_per_chunk", ctx) == 7.0
    shed = dict(ctx, counts=dict(counts, shed_lanes=1024))
    assert round(read("storm_shed_lane_share", shed), 3) == 3.03
    # The parent's program: sched.coalesce and sched.dispatch are there, the
    # dispatcher's own pack / wait / complete spans and the counter are not.
    parents = {
        "spans": [r for r in flush if r[1] in ("sched.queue", "sched.coalesce", "sched.dispatch")],
        "counts": {"operations": 2, "live_lanes": 33792},
        "ledger": None,
    }
    for name in ("storm_flush_host_ms", "storm_shed_lane_share", "storm_quiet_tenant_ms"):
        assert read(name, parents) is None, name
    # No spans at all (an untraced run, a program without the scheduler).
    for name in NEW_IN_PR_54:
        if files[name][1]["source"] == "program_span":
            assert read(name, {"spans": None, "counts": {"operations": 2}}) is None, name
            assert read(name, {"spans": [], "counts": {}}) is None, name


SMALL = {
    "chains": [
        {"validators": 4, "count": 2, "quorum": 3},
        {"validators": 6, "count": 1, "quorum": 5},
        {"validators": 10, "count": 1, "quorum": 7},
    ],
    "seals_per_block": {"4": 4, "6": 6, "10": 10},
    "mix": {
        "tenants": 4, "blocks_per_call": 4, "corrupt_every": 4, "trace_seconds": 0.2,
        "corrupt_seals": {"4": 1, "6": 1, "10": 2}, "cross_chain_seals": {"4": 1, "10": 1},
    },
}


def _run(monkeypatch, trace: int, seconds: float, route: str, **mix_overrides):
    """The cell through ``run.run`` at four small chains, the platform check
    patched HERE (the command has no CPU mode)."""
    import jax

    real = bench_run.load_cell

    def load(name, root=bench_run.ROOT):
        got = real(name, root)
        cfg, mix = got["config"], got["traffic"]
        cfg["chains"], cfg["validators"] = SMALL["chains"], 24
        cfg["assumed"]["seals_per_block"] = SMALL["seals_per_block"]
        # An 8-lane dispatch cap: the one recover program XLA:CPU has to build.
        cfg["sched"].update(route=route, max_dispatch_lanes=8)
        mix.update(SMALL["mix"])
        mix.update(mix_overrides)
        return got

    monkeypatch.setattr(
        bench_run, "require_tpu",
        lambda chips: {"platform": jax.devices()[0].platform, "kind": "TPU v5 lite", "count": 1},
    )
    monkeypatch.setattr(bench_run, "load_cell", load)
    out = io.StringIO()
    args = bench_run.argparse.Namespace(workload=CELL, seed=2147483659, seconds=seconds, trace=trace)
    try:
        with contextlib.redirect_stdout(out):
            rc = bench_run.run(args)
    finally:
        gc.unfreeze()
        shutil.rmtree(os.path.join(ROOT, ".cache", "benchmark_trace", CELL), ignore_errors=True)
    return rc, [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]


COMPARED = {
    "operations_failed", "compiles_in_window", "guarantees_broken",
    "mask_lanes_off_the_labels", "reference_lanes_off_the_labels", "oracle_lanes_off_the_labels",
    "cross_chain_seals_not_planted", "cross_chain_seals_accepted",
    "cross_chain_seals_refused_by_their_own_chain", "sub_quorum_ranges_accepted",
    "other_tenants_failed_beside_the_refused_range", "window_shed_lanes", "window_flush_faults",
    "window_unpinned_launches", "window_table_cuts", "flush_faults", "shed_lanes",
}


def test_rehearsal_on_the_device_route_reports_the_rate_and_every_comparison_beside_its_limit(monkeypatch):
    rc, lines = _run(monkeypatch, 0, 0.5, "device")
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sync_sigs_per_s", "setup_s"}
    assert result["metrics"]["sync_sigs_per_s"]["value"] > 0
    assert list(result)[-1] == "compared" and set(result["compared"]) == COMPARED
    assert all(c == {"value": 0, "limit": 0} for c in result["compared"].values())
    assert info["driver"] == "sync_storm" and info["lanes_per_storm"] == 96
    assert info["committees"] == [4, 4, 6, 10] and info["boot"]["shapes"] == [[8, 8]]
    assert set(info["sched_stats"]["served"]) == {"device/8"}
    assert info["check_cross_chain_lanes"] == 3 and "quorum" in info["sub_quorum_refused"]


def test_the_traced_rehearsal_reports_every_storm_metric(monkeypatch):
    """On the host route (no device trace on this CPU: the kernel readers
    leave their metrics out; the spans and counters are all there)."""
    rc, lines = _run(monkeypatch, 1, 0.6, "host")
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert "sync_sigs_per_s" not in got and not set(NOT_JOINED) & set(got)
    assert set(NEW_IN_PR_54) <= set(got)
    assert got["storm_shed_lane_share"] == 0.0 and got["compiles_in_window"] == 0
    assert 1.0 <= got["storm_tenants_per_flush"] <= 4.0
    assert got["storm_lanes_per_sched_dispatch"] <= 8  # the rehearsal's dispatch cap
    assert got["storm_quiet_tenant_ms"] > 0 and got["storm_flush_host_ms"] > 0
    assert result["metrics"]["storm_quiet_tenant_ms"]["unit"] == "ms"


def test_a_storm_whose_mask_is_off_the_labels_is_a_failed_operation(monkeypatch):
    """The control: the labels of one tenant flipped after signing, so every
    storm's own verdicts are off them and the run reads ``correct`` false."""
    from benchmark.drivers import sync_storm as driver

    warm = driver.warm

    def flipped(st):
        warm(st)
        for r in range(len(st.want)):
            st.want[r][0] = ~st.want[r][0]

    monkeypatch.setattr(driver, "warm", flipped)
    rc, lines = _run(monkeypatch, 0, 0.3, "host")
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
    assert "off the labels" in info["storm_errors"][0]
    assert result["compared"]["mask_lanes_off_the_labels"]["value"] > 0
