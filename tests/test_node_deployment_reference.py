"""The ``ecdsa-100v-node`` deployment against the plain reference, small, on
the CPU (ISSUE 39).

One ``ValidatorNode`` of a 10-validator committee, built from a ``node.toml``
and embedded as the benchmark's driver ``node`` embeds it, through the
driver's own entry points (``setup``, ``warm``, ``measure``, ``check``): its 9
peers on real loopback sockets in two peer-host processes, 3 of them
corrupted every height, the scheduler's dispatcher on ``route = "device"`` so
that floods this small really take the device route (the 8- and 32-lane
recover programs, real kernels), the write-ahead log on disk.  Against it,
upstream's sequential path: an ``IBFT`` with NO batch verifier fed the same
messages one at a time (``tests/test_deployment_reference.py``'s reference),
and the WAL replayed from disk.  Every tolerance is exact: with 3 of 9 peers
corrupted the valid PREPAREs are exactly the quorum and the valid seals
exactly the quorum, so neither set depends on the order of arrival.
"""

import gc
import json
import math
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmark.drivers import node as driver  # noqa: E402
from test_deployment_reference import (  # noqa: E402  (beside this file)
    CORRUPT_SHARE,
    HEIGHTS,
    _finalized as reference_finalized,
    _run as reference_run,
)

from go_ibft_tpu.chain.wal import WriteAheadLog  # noqa: E402
from go_ibft_tpu.obs import ledger as cost_ledger  # noqa: E402
from go_ibft_tpu.obs import trace  # noqa: E402
from go_ibft_tpu.verify import HostBatchVerifier  # noqa: E402

N, SEED = 10, 3900000131


def node_run(n: int, seed: int, route: str, heights: int = HEIGHTS, **mix_says):
    """``heights`` heights of the deployment at ``n`` validators as the
    driver runs the cell, on a configuration and a mix sized to sign exactly
    that many (``mix_says``: further keys of the mix): ``setup``, ``warm``
    (the node's boot, no warm heights),
    ``measure`` with the span ring and the cost ledger on until the signed
    heights run out, ``check`` (the drain, the labels, the WAL read back)."""
    config = {
        "validators": n,
        "base_round_timeout_s": 60,
        "node_toml": {
            "consensus": {"listen": "127.0.0.1:0"},
            "proof_api": {"listen": ""},
            "telemetry": {"listen": ""},
            "sched": {"enabled": True, "route": route},
        },
        "assumed": {"peer_hosts": 2},
    }
    mix = {
        "corrupt_share": CORRUPT_SHARE,
        "warm_heights": 0,
        "presign_msgs_per_s": 2 * n * heights,
        **mix_says,
    }
    trace.enable()  # before setup: the node's [trace] follows the harness
    cost_ledger.enable()
    st = driver.setup(config, mix, seed, 1.0)
    try:
        assert st.heights == heights
        driver.warm(st)
        st.boot_rows = cost_ledger.snapshot()["dispatches"]
        st.samples, st.counts = driver.measure(st, math.inf)
        st.rows = cost_ledger.snapshot()["dispatches"]
        st.records = trace.recorder().snapshot()  # the node's ring by now
        assert trace.recorder().dropped == 0
    finally:
        cost_ledger.disable()
        trace.disable()
        gc.unfreeze()  # ``warm`` froze the heap for the measured window
        try:
            st.heights_finalized = list(st.finalized)
            st.checked = driver.check(st)
        finally:
            st.hosts.close()
    return st


@pytest.fixture(scope="module")
def node():
    st = node_run(N, SEED, "device")
    yield st
    shutil.rmtree(st.data_dir, ignore_errors=True)


def test_the_embedded_node_over_sockets_equals_the_sequential_reference(node):
    assert node.counts["failed"] == 0 and len(node.samples) == HEIGHTS
    assert node.counts["ran_out_of_presigned"] is True
    assert node.checked is True, (node.info.get("guarantee_broken"), node.info["compared"])
    assert all(c == {"value": 0, "limit": 0} for c in node.info["compared"].values())
    reference = reference_run(N, SEED, HostBatchVerifier, batched=False)
    assert reference.checked is True and reference.counts["failed"] == 0
    got = {
        t.height: (
            proposal.raw_proposal,
            proposal.round,
            frozenset(node.locked[t.height][1]),
            frozenset(s.signer for s in seals),
        )
        for t, proposal, seals in node.heights_finalized
    }
    assert got == reference_finalized(reference)
    corrupt = driver.corrupted_peers(N, CORRUPT_SHARE)
    assert corrupt == 3
    for t, _proposal, _seals in node.heights_finalized:
        _block, round_, prepared, signers = got[t.height]
        assert round_ == 0 and node.locked[t.height][0] == 0
        assert not prepared & t.bad and not signers & t.bad
        # Exactly the quorum on both sides, whatever order the sockets gave.
        assert len(prepared) == node.committee.quorum - 1
        assert len(signers) == N - corrupt == node.committee.quorum


def test_the_wal_replayed_from_disk_is_the_window(node):
    state = WriteAheadLog(node.node.wal_path).replay()
    assert not state.dropped_tail and state.lock is None
    assert [b.height for b in state.blocks] == list(range(1, HEIGHTS + 1))
    for block, (t, proposal, seals) in zip(state.blocks, node.heights_finalized):
        assert block.height == t.height
        assert block.proposal.encode() == proposal.encode()
        assert [(s.signer, s.signature) for s in block.seals] == [
            (s.signer, s.signature) for s in seals
        ]
    with open(node.node.wal_path, "rb") as fh:
        kinds = [(json.loads(line)["kind"], json.loads(line)["height"]) for line in fh]
    assert kinds == [(k, h) for h in range(1, HEIGHTS + 1) for k in ("lock", "finalize")]
    assert node.info["wal"]["lock_records"] == HEIGHTS
    assert node.info["rpcs_in"] == node.info["peer_sends"]["acked"] > 0


def test_the_device_route_was_taken_at_the_widths_boot_loaded(node):
    assert node.info["sched_route"] == "device"
    assert node.info["recover_widths_loaded"] == [8, 32]  # bucket(10) = 32
    window = {(r["program"], r["route"]): r for r in node.rows}
    recovers = window[("ecdsa_recover", "device")]
    assert ("ecdsa_recover", "host") not in window  # route = "device": nothing shed
    assert recovers["dispatches"] >= 3 * HEIGHTS  # the PREPARE and COMMIT envelopes, the seals
    # A launch is 8 or 32 lanes wide: nothing boot did not load.
    served = node.info["sched"]["served"]
    assert set(served) <= {"device/8", "device/32"} and sum(served.values()) == recovers["dispatches"]
    assert node.info["sched"]["flush_faults"] == 0


def test_the_new_spans_are_counted(node):
    spans = [r for r in node.records if r[0] == "X"]
    appends = [r[5] for r in spans if r[1] == "wal.append"]
    assert [(a["kind"], a["height"]) for a in appends] == [
        (k, h) for h in range(1, HEIGHTS + 1) for k in ("lock", "finalize")
    ]
    assert all(a["fsync_ms"] > 0 for a in appends)  # fsync_locks=True, the default
    queued = [r for r in spans if r[1] == "sched.queue"]
    dispatched = [r for r in spans if r[1] == "sched.dispatch"]
    # One ``sched.queue`` a request of the handle, closed by the flush that
    # took it; one tenant, so never more flushes than requests.
    tenant = node.node.scheduler.stats()["tenants"]["node0/consensus"]
    assert len(queued) == tenant["requests"]
    assert 0 < len(dispatched) <= len(queued)
    assert {r[5]["tier"] for r in queued} == {"consensus"}
    assert sum(r[5]["lanes"] for r in queued) == sum(r[5]["lanes"] for r in dispatched)


def test_a_third_of_the_peers_silent_and_no_phase_waits_out_the_cap():
    """25 validators on ``route = "auto"`` (quorum 17: the node holds votes
    for the 16 others it takes), the tolerated 8 peers never voting: the 16
    that do are exactly enough, so a phase is handed over as it completes or
    when the sockets go quiet, and never held to the cap."""
    from go_ibft_tpu.core import BatchingIngress

    st = node_run(25, SEED + 1, "auto", heights=2, corrupt_share=0.0, silent_peers=8)
    try:
        assert st.checked is True, (st.info.get("guarantee_broken"), st.info["compared"])
        assert st.counts["failed"] == 0 and len(st.samples) == 2
        assert st.node.ingress.burst_hint == st.committee.quorum - 1 == 16
        waits = [r for r in st.records if r[0] == "X" and r[1] == "ingress.wait"]
        whys = {r[5]["why"] for r in waits}
        assert whys & {"full", "quiet"} and "capped" not in whys, whys
        # Held votes never meet the held-back rule, and since PR 41 neither
        # does the PREPREPARE that follows a COMMIT phase's device flush: it
        # goes out alone at the end of its turn.
        assert whys <= {"soon", "full", "quiet"}, whys
        assert [r[5]["lanes"] for r in waits if r[5]["why"] == "soon"] == [1, 1]
        cap_us = BatchingIngress.BURST_HOLD_FACTOR * st.node.ingress.max_delay * 1e6
        assert max(r[4] for r in waits) < cap_us / 2
        # 16 of 24 peers voted, twice a height; the proposers' PREPREPAREs besides.
        assert st.info["peer_sends"]["sent"] <= 2 * (2 * 16 + 1)
    finally:
        shutil.rmtree(st.data_dir, ignore_errors=True)


@pytest.mark.parametrize(
    "n, sched, route, hint",
    [
        (100, True, "auto", 66),  # the quorum less this node's own vote
        (100, True, "device", 66),
        (100, True, "host", None),  # the default route: the host verifies as votes come
        (100, False, "auto", None),  # no scheduler: the host oracle
        (10, True, "device", None),  # 6 votes decide: under the 16-lane cutover
    ],
)
def test_the_node_holds_votes_only_where_a_flush_can_reach_the_device(tmp_path, n, sched, route, hint):
    from benchmark.lib.committee import Committee
    from go_ibft_tpu.node import ValidatorNode
    from go_ibft_tpu.node.config import ConsensusConfig, NodeConfig

    committee = Committee(n, 7)
    cfg = NodeConfig(
        node_id=0,
        key_seed=committee.seeds[0],
        data_dir=str(tmp_path),
        validators={a.hex(): p for a, p in committee.powers.items()},
        consensus=ConsensusConfig(listen="127.0.0.1:0", peers={}),
        sched_enabled=sched,
        sched_route=route,
    )
    node = ValidatorNode(cfg, install_signal_handlers=False)
    assert node.ingress.burst_hint == hint


def test_the_votes_a_quorum_takes_follow_the_powers():
    from go_ibft_tpu.node.node import votes_to_quorum

    equal = {bytes([i]): 1 for i in range(100)}
    assert votes_to_quorum(equal, bytes([0])) == 66
    # Total 130, quorum 87: with its own 1 the node needs 86 more, and the
    # heaviest others (31, then 1 each) give that in 1 + 55 votes.
    skewed = dict(equal)
    skewed[bytes([1])] = 31
    assert votes_to_quorum(skewed, bytes([0])) == 56
    assert votes_to_quorum(skewed, bytes([1])) == 56  # 87 - 31, one vote each
