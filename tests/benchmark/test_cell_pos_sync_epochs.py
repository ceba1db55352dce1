"""The cell ``ecdsa-100v-pos.sync-epochs`` (PR 45): the real files through
``run.load_cell``, the deployment and its traffic arithmetic, the generator's
labels, its new readers with and without what they read, a CPU rehearsal of
the cell's control flow at eight validators of a ten-account pool (no number
of which is a device number), and the controls: a guarantee broken between
the window and ``check`` reads ``correct`` false."""

import contextlib
import gc
import io
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402  (benchmark/run.py)
from benchmark.drivers import sync_epochs as driver  # noqa: E402
from benchmark.lib import pos_reference  # noqa: E402
from benchmark.lib.readers import read_metric  # noqa: E402
from benchmark.lib.rotating_committee import RotatingCommittee  # noqa: E402
import contract_predicates as contract  # noqa: E402  (beside this file)

CELL = contract.POS_SYNC
NEW_IN_PR_45 = contract.NEW_IN_PR_45


def declared(root: str = ROOT) -> None:
    """All this file holds of ``BENCHMARK.json``, wherever the entries stand
    and whatever a later PR appended (PR 50): the copy test of
    ``test_benchmark_contract.py`` finds this function by glob and calls it on
    a tree with a further cell in it."""
    contract.pos_sync(root)


@pytest.fixture(scope="module")
def cell():
    return bench_run.load_cell(CELL)


def test_the_cell_and_its_configuration_are_declared_and_break_no_accepted_cells_contract(cell):
    # Declared with its configuration on one chip; selects, in order, what PR 45
    # gave it; its five entries are declared in the order PR 45 appended them and
    # list it; the sync readers it joined and the rate still list it; the cells
    # accepted before it get none of the five.
    declared()
    contract.every_accepted_cell()  # with this cell in place
    assert len(cell["cell"]["why"]) <= 200 and "13-14 validator sets" in cell["cell"]["why"]


def test_the_deployment_states_its_guarantees_and_its_traffic_adds_up(cell):
    cfg, mix = cell["config"], cell["traffic"]
    entry = next(c for c in contract.bench_of()["configs"] if c["name"] == "ecdsa-100v-pos")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for cited in ("validator_manager.go:17-20,129-135", "validator_manager_test.go:11-193", "recalled"):
        assert cited in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["peers"] and cfg["architecture"] is None
    assert cfg["scheme"] == "ecdsa-secp256k1" and cfg["base_round_timeout_s"] == 10
    assert (cfg["validators"], cfg["epoch_blocks"]) == (100, 10)
    a = cfg["assumed"]
    assert (a["staked_pool"], a["seals_per_block"], a["churn_accounts"]) == (120, 80, 30)
    assert a["stake_pareto_shape"] == 1.2 and a["stake_clip_tokens"] == [1, 10**6]
    assert a["churn_factor_permille"] == [500, 2000]
    assert a["message_delay_ms"] == 0 and a["block_time_s"] == 0
    # The union of a range's sets fits the table bucket the accepted readers
    # derive from ``validators`` (128 rows: both sync cells' executable).
    assert cfg["validators"] <= a["staked_pool"] <= 128
    sync = cfg["guarantees"]["sync"]
    for said in ("ITS OWN height", "quorum POWER", "neighbouring epoch", "exact integers", "no float"):
        assert said in sync
    assert {"sync", "finality", "durability", "correct_means"} <= set(cfg["guarantees"])
    # Traffic, as ISSUE 45 states it.
    assert mix["driver"] == "sync_epochs" and mix["lanes_per_call"] == 10240 == 128 * 80
    assert mix["range_starts"] == [1, 129, 257]
    assert (mix["corrupt_every"], mix["corrupt_mangled"], mix["corrupt_out_of_set"]) == (8, 2, 1)
    assert mix["warm_calls_per_range"] == 1 and mix["check_lanes"] == 2048
    blocks = mix["lanes_per_call"] // a["seals_per_block"]
    epoch = lambda h: (h - 1) // cfg["epoch_blocks"]  # noqa: E731
    assert [epoch(s + blocks - 1) - epoch(s) + 1 for s in mix["range_starts"]] == [13, 14, 14]
    for s in mix["range_starts"]:
        bad = [h for h in range(s, s + blocks) if h % mix["corrupt_every"] == 0]
        assert len(bad) == 16  # 48 refused lanes a call, 16 of them out-of-set
    assert round(100 * 16 / 10240, 3) == 0.156


def test_the_generator_is_a_pure_function_of_the_seed_and_labels_what_it_made():
    cfg = {
        "validators": 8,
        "epoch_blocks": 2,
        "assumed": dict(
            staked_pool=10, seals_per_block=8, stake_pareto_shape=1.2,
            stake_clip_tokens=[1, 10**6], churn_accounts=4, churn_factor_permille=[500, 2000],
        ),
    }
    big = 2**31 + 12345  # the driver's seeds are over 32 signed bits
    a, b = RotatingCommittee(cfg, big), RotatingCommittee(cfg, big)
    span = (2, 13)
    blocks, want, outside = a.sync_blocks(2, 12, span, 2, 1, 1)
    again, _w, _o = b.sync_blocks(2, 12, span, 2, 1, 1)
    assert [(x.height, x.proposal.raw_proposal, x.seals) for x in blocks] == [
        (x.height, x.proposal.raw_proposal, x.seals) for x in again
    ]
    other, _w, _o = RotatingCommittee(cfg, big + 1).sync_blocks(2, 12, span, 2, 1, 1)
    assert [x.seals for x in other] != [x.seals for x in blocks]
    for block, mask, n_out in zip(blocks, want, outside):
        powers = a.src(block.height)
        assert len(powers) == 8 and min(powers.values()) >= 10**18 > 2**31
        assert len({s.signer for s in block.seals}) == len(block.seals) == 8
        assert list(mask) == pos_reference.block_mask(block, a.src)
        got, quorum = pos_reference.block_power(block, a.src)
        assert got >= quorum == (2 * sum(powers.values())) // 3 + 1
        bad = block.height % 2 == 0
        assert int((~mask).sum()) == (2 if bad else 0) and n_out <= (1 if bad else 0)
        strangers = [s.signer for s in block.seals if s.signer not in powers]
        assert len(strangers) == (1 if bad else 0)
    assert sum(outside) >= 1  # some out-of-set seal sits in another set of the range
    # The active set is the largest stakes, and the sets move.
    stakes = a.stakes(3)
    assert min(stakes[x] for x in a.powers(3)) >= max(stakes[x] for x in stakes if x not in a.powers(3))
    assert len({a.members(e) for e in range(8)}) > 1


def _ctx(spans=(), counts=None):
    return {"spans": list(spans), "counts": dict(counts or {}), "ledger": None}


def test_every_new_reader_reads_what_it_names_and_nothing_where_that_is_absent(cell):
    files = {m["name"]: (path, m) for path, m in cell["per_layer"]}
    verify = lambda **args: ("X", "chain.sync.verify", "t", 0, 5000, args)  # noqa: E731
    with_all = _ctx(
        [
            verify(lanes=10240, heights=128, drains=1, sets=13, table_rows=118),
            verify(lanes=10240, heights=128, drains=1, sets=14, table_rows=119),
            verify(lanes=10240, heights=128, drains=1, sets=14, table_rows=119),
            ("X", "chain.sync.tally", "t", 0, 1500, {"blocks": 128, "bigint": True}),
            ("X", "chain.sync.tally", "t", 0, 2500, {"blocks": 128, "bigint": True}),
        ],
        {"operations": 3, "live_lanes": 30720, "table_uploads": 0, "out_of_set_lanes": 48},
    )
    read = lambda name, ctx: read_metric(ctx, files[name][1], files[name][0])  # noqa: E731
    assert read("pos_drains_per_call", with_all) == 1
    assert read("pos_sets_per_call", with_all) == 14
    assert read("pos_tally_ms_per_call", with_all) == pytest.approx(2.0)
    assert read("pos_table_uploads_per_call", with_all) == 0.0
    assert read("pos_out_of_set_lane_share", with_all) == pytest.approx(0.15625)
    # The parent's program: the span has ``drains`` and no ``sets``, there is
    # no tally span and no counter; each metric is left out, none raises.
    parents = _ctx(
        [("X", "chain.sync.verify", "t", 0, 5000, {"lanes": 10240, "heights": 128, "drains": 13})],
        {"operations": 3, "live_lanes": 30720},
    )
    assert read("pos_drains_per_call", parents) == 13
    for name in NEW_IN_PR_45[1:]:
        assert read(name, parents) is None, name
    # ... and a run with no spans at all (``--trace 0`` never asks, a window
    # that recorded nothing does).
    for name in NEW_IN_PR_45:
        assert read(name, _ctx()) is None, name
        assert read(name, {"spans": None, "counts": {}}) is None, name


def test_on_a_program_with_one_height_a_call_the_driver_asks_block_by_block(monkeypatch):
    """The parent of this PR under these files: no ``LaneHeights``, no
    counters.  The mask is asked a block a call, the counters read ``None``."""
    from go_ibft_tpu.verify import batch

    asked = []

    class OneHeight:
        def verify_seal_lanes(self, lanes, height):
            assert isinstance(height, int)
            asked.append((len(lanes), height))
            return np.ones(len(lanes), dtype=bool)

    cfg = bench_run.load_cell(CELL)["config"]
    cfg = dict(cfg, validators=8, epoch_blocks=2, assumed=dict(cfg["assumed"], staked_pool=10, seals_per_block=8, churn_accounts=4))
    blocks, _w, _o = RotatingCommittee(cfg, 7).sync_blocks(1, 4, (1, 4), 0, 0, 0)
    monkeypatch.delattr(batch, "LaneHeights")
    monkeypatch.delattr(batch, "TABLE_UPLOADS_KEY")
    monkeypatch.delattr(batch, "SYNC_OUT_OF_SET_LANES_KEY")
    assert driver.range_mask(OneHeight(), blocks).all()
    assert asked == [(8, 1), (8, 2), (8, 3), (8, 4)]
    assert driver._counters() == {"table_uploads": None, "out_of_set_lanes": None}


def test_what_a_traced_runs_lead_in_counts_is_taken_out_of_the_window(monkeypatch):
    """The lead-in dispatch runs inside ``measure`` and is no operation: its
    table upload and its out-of-set lanes are not the window's (the first
    traced run on the chip read 3 lanes off the labels for them)."""
    from types import SimpleNamespace

    from benchmark.lib.harness import Probe
    from go_ibft_tpu.utils import metrics
    from go_ibft_tpu.verify import batch

    class LeadsOnce(Probe):
        """One lead-in, then two operations past the window's end."""

        turns = 0

        def boundary(self, elapsed_s, left=None):
            self.turns += 1

        def wants_more(self):
            return self.turns <= 3

        def leading(self):
            return self.turns == 1

    def lead_in(st):
        metrics.inc_counter(batch.TABLE_UPLOADS_KEY)
        metrics.inc_counter(batch.SYNC_OUT_OF_SET_LANES_KEY, 3)

    def call(st, k):
        metrics.inc_counter(batch.SYNC_OUT_OF_SET_LANES_KEY, st.outside[k])
        return 0.01

    monkeypatch.setattr(driver, "_one_dispatch", lead_in)
    monkeypatch.setattr(driver, "_call", call)
    st = SimpleNamespace(
        probe=LeadsOnce(), starts=[1, 5], outside=[2, 1], blocks_per_call=4, spb=8, info={}
    )
    samples, counts = driver.measure(st, 0.0)
    assert len(samples) == 2 == counts["operations"] and counts["live_lanes"] == 64
    assert counts["table_uploads"] == 0  # the lead-in's upload is not the window's
    assert counts["out_of_set_lanes"] == 3 == st.outside_in_window  # 2 + 1, not 6


def _tiny(real=bench_run.load_cell):
    """``load_cell`` with the deployment cut to a size the CPU runs in
    seconds: eight validators of a ten-account pool, epochs of two blocks,
    calls of four blocks from heights that are not epoch-aligned."""

    def load(name, root=bench_run.ROOT):
        cell = real(name, root)
        cfg, mix = cell["config"], cell["traffic"]
        cfg.update(validators=8, epoch_blocks=2)
        cfg["assumed"].update(staked_pool=10, seals_per_block=8, churn_accounts=4)
        mix.update(
            lanes_per_call=32, check_lanes=32, range_starts=[2, 6, 10],
            corrupt_every=2, corrupt_mangled=1, corrupt_out_of_set=1, trace_seconds=0.3,
        )
        return cell

    return load


def _run(monkeypatch, trace: int, seconds: float, seed: int = 2147483659):
    import jax

    monkeypatch.setattr(
        bench_run,
        "require_tpu",
        lambda chips: {"platform": jax.devices()[0].platform, "kind": "TPU v5 lite", "count": 1},
    )
    monkeypatch.setattr(bench_run, "load_cell", _tiny())
    out = io.StringIO()
    args = bench_run.argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=trace)
    try:
        with contextlib.redirect_stdout(out):
            rc = bench_run.run(args)
    finally:
        gc.unfreeze()
        shutil.rmtree(os.path.join(ROOT, ".cache", "benchmark_trace", CELL), ignore_errors=True)
    return rc, [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]


ALL_COMPARED = {
    "operations_failed", "compiles_in_window", "guarantees_broken",
    "mask_lanes_off_the_labels", "reference_lanes_off_the_labels", "oracle_lanes_off_the_labels",
    "out_of_set_lanes_off_the_labels", "heaviest_cut_ranges_accepted",
    "outsider_padded_ranges_accepted", "lightest_cut_ranges_accepted",
}


def test_rehearsal_reports_the_rate_and_every_comparison_beside_its_limit(monkeypatch):
    rc, lines = _run(monkeypatch, 0, 0.5)
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sync_sigs_per_s", "setup_s"}
    assert result["metrics"]["sync_sigs_per_s"]["unit"] == "sigs/s"
    assert list(result)[-1] == "compared" and set(result["compared"]) == ALL_COMPARED
    assert all(c == {"value": 0, "limit": 0} for c in result["compared"].values())
    assert info["driver"] == "sync_epochs" and info["lanes_per_call"] == 32
    assert info["cutover"] == 16 and info["cutover_source"] == "default"
    assert len(info["warm_calls_s"]) == 3  # one call a range
    assert info["check_invalid_lanes"] == 4  # two bad blocks of range 0, two bad seals each
    traps = info["traps"]
    assert traps["clean_power"] >= traps["quorum_power"] > 2**60
    for name in ("heaviest_cut_ranges_accepted", "outsider_padded_ranges_accepted", "lightest_cut_ranges_accepted"):
        assert traps[name]["power"] < traps["quorum_power"]
        assert f"height {traps['height']}" in traps[name]["refused"]


def test_the_traced_rehearsal_reports_the_new_metrics_beside_the_joined_ones(monkeypatch):
    rc, lines = _run(monkeypatch, 1, 1.0)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_IN_PR_45) <= set(got) and "sync_sigs_per_s" not in got
    assert got["pos_drains_per_call"] == 1  # three epochs a call, ONE drain
    assert 1 <= got["pos_sets_per_call"] <= 3
    assert got["pos_table_uploads_per_call"] == 0.0  # every range's table went up in warm-up
    assert 0.0 <= got["pos_out_of_set_lane_share"] <= 100 * 2 / 32
    assert got["pos_tally_ms_per_call"] > 0
    assert got["sync_device_lane_share"] == 100.0 and got["sync_lane_occupancy"] == 100.0
    assert {"sync_host_ms_per_call", "sync_pack_ms_per_chunk", "sync_device_wait_ms_per_chunk"} <= set(got)
    assert got["compiles_in_window"] == 0


def _accepting_trap(n):
    """The ``n``-th range that must be refused goes through instead."""

    def plant(monkeypatch, st):
        real, calls = driver._refused, []

        def refused(st_, blocks):
            calls.append(1)
            return (False, "") if len(calls) == n + 1 else real(st_, blocks)

        monkeypatch.setattr(driver, "_refused", refused)

    return plant


def _with_one_mask_lane_flipped(monkeypatch, st):
    real = driver.range_mask

    def flipped(verifier, blocks):
        mask = real(verifier, blocks)
        mask[3] = ~mask[3]
        return mask

    monkeypatch.setattr(driver, "range_mask", flipped)


def _with_an_out_of_set_lane_uncounted(monkeypatch, st):
    st.counts["out_of_set_lanes"] -= 1


@pytest.mark.parametrize(
    "plant, reads",
    [
        (_accepting_trap(0), {"heaviest_cut_ranges_accepted": 1}),
        (_accepting_trap(1), {"outsider_padded_ranges_accepted": 1}),
        (_accepting_trap(2), {"lightest_cut_ranges_accepted": 1}),
        (_with_one_mask_lane_flipped, {"mask_lanes_off_the_labels": 1}),
        (_with_an_out_of_set_lane_uncounted, {"out_of_set_lanes_off_the_labels": 1}),
    ],
    ids=["heaviest-cut", "outsiders", "lightest-cut", "mask-lane", "counter"],
)
def test_a_broken_guarantee_reads_incorrect_through_the_harness(monkeypatch, plant, reads):
    """The controls: what the configuration guarantees, broken between the
    window and ``check``, comes out as ``correct`` false, by the comparison
    that guards it."""
    real_check = driver.check

    def check(st):
        plant(monkeypatch, st)
        return real_check(st)

    monkeypatch.setattr(driver, "check", check)
    rc, lines = _run(monkeypatch, 0, 0.3)
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is False and result["failed"] == 0
    off = {k: c["value"] for k, c in result["compared"].items() if c["value"] != c["limit"]}
    assert off.pop("guarantees_broken") == 1
    assert off == reads  # that comparison, and no other
    assert "off their limits" in info["guarantee_broken"]
