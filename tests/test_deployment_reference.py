"""The ``ecdsa-300v`` deployment against the plain reference, small, on the
CPU (ISSUE 31).

One validator of a committee whose other members are pre-signed peers, 30%
of them corrupted every height, driven by the benchmark's driver ``round0``
through its own entry points (``setup``, ``warm``, ``measure``, ``check``):
once through ``IBFT`` + ``BatchingIngress`` + the batch verifier with the
device route really taken (the 8- and 32-lane recover programs, real
kernels), once through an ``IBFT`` with NO batch verifier fed message by
message, which is upstream's sequential path and this system's plain
reference.  Both must finalize the same blocks in round 0 from the same
PREPARE senders and the same seals, none of them a corrupted peer's, and the
``verify.verdicts`` instants must count what the generator labelled.

The last case is a committee just over the ingress's old 256-message
ceiling, on the host route: every phase one flush.
"""

import gc
import math
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drivers import round0 as driver  # noqa: E402

from go_ibft_tpu.obs import ledger as cost_ledger  # noqa: E402
from go_ibft_tpu.obs import trace  # noqa: E402
from go_ibft_tpu.verify import AdaptiveBatchVerifier, HostBatchVerifier  # noqa: E402

HEIGHTS = 2
CORRUPT_SHARE = 0.3  # benchmark/traffic/flood-byz30.json


def _run(
    n: int,
    seed: int,
    make_verifier,
    batched: bool = True,
    corrupt_share: float = CORRUPT_SHARE,
):
    """``HEIGHTS`` heights of the deployment at ``n`` validators, as the
    driver runs a cell: its ``setup`` on a configuration and a traffic mix
    sized to sign exactly ``HEIGHTS`` heights, its ``warm`` (no warm
    heights), its ``measure`` until the signed heights run out, its
    ``check``.  The driver's default-constructed verifier is replaced by
    ``make_verifier``'s, so that floods this small still take the route
    under test.  Returns the driver's state and what the window recorded."""
    config = {"validators": n, "base_round_timeout_s": 60}
    mix = {
        "corrupt_share": corrupt_share,
        "warm_heights": 0,
        "presign_msgs_per_s": 2 * n * HEIGHTS,
    }
    st = driver.setup(config, mix, seed, 1.0)
    assert st.heights == HEIGHTS
    assert st.corrupt == driver.corrupted_peers(n, corrupt_share)
    st.verifier = make_verifier(st.committee.src)
    if batched:
        st.engine.batch_verifier = st.verifier
    else:
        # Upstream's AddMessage: one message, verified and stored, at a time.
        st.ingress.close()
        st.engine.batch_verifier = None
        st.ingress = SimpleNamespace(submit=st.engine.add_message)
    try:
        driver.warm(st)
        rec = trace.enable()
        cost_ledger.enable()
        try:
            st.samples, st.counts = driver.measure(st, math.inf)
            st.rows = cost_ledger.snapshot()["dispatches"]
            st.records = rec.snapshot()
            assert rec.dropped == 0
        finally:
            cost_ledger.disable()
            trace.disable()
            if batched:
                st.ingress.close()
    finally:
        gc.unfreeze()  # ``warm`` froze the heap for the measured window
    assert st.counts["ran_out_of_presigned"] and st.counts["attempted"] == HEIGHTS
    st.labels = [st.committee.traffic(h, st.corrupt) for h in range(1, HEIGHTS + 1)]
    st.heights_finalized = list(st.finalized)
    st.checked = driver.check(st)  # labels + the sequential host oracle
    return st


def _finalized(st) -> dict:
    """height -> (block, round, locked PREPARE senders, finalized signers)."""
    return {
        t.height: (
            proposal.raw_proposal,
            proposal.round,
            frozenset(st.locked[t.height]),
            frozenset(s.signer for s in seals),
        )
        for t, proposal, seals in st.heights_finalized
    }


def _verdicts(st, kind: str) -> list:
    return [
        (r[5]["live"], r[5]["rejected"])
        for r in st.records
        if r[1] == "verify.verdicts" and r[5]["kind"] == kind
    ]


@pytest.mark.parametrize("n, seed", [(10, 3000000131), (20, 77)])
def test_batched_device_route_equals_the_sequential_reference(n, seed):
    corrupt = driver.corrupted_peers(n, CORRUPT_SHARE)
    assert corrupt == {10: 3, 20: 6}[n]
    # cutover 2: the one-lane PREPREPARE stays on the host, every flood
    # takes the device route at twice the 32-lane bucket (ISSUE 32: the
    # second half is free there, and the COMMIT flood's seals ride in it),
    # and the engine's seal drain dispatches nothing.
    batched = _run(n, seed, lambda src: AdaptiveBatchVerifier(src, cutover_lanes=2))
    reference = _run(n, seed, HostBatchVerifier, batched=False)
    for st in (batched, reference):
        assert st.counts["failed"] == 0 and len(st.samples) == HEIGHTS
        assert st.checked is True, st.info.get("guarantee_broken")
    assert batched.counts["flush_sizes"] == [1, n - 1, n] * HEIGHTS
    got, want = _finalized(batched), _finalized(reference)
    assert got == want
    quorum = batched.committee.quorum
    for t in batched.labels:
        block, round_, prepared, signers = got[t.height]
        assert round_ == 0
        assert not prepared & t.bad and not signers & t.bad
        # Every honest follower's PREPARE is in the certificate beside the
        # node's own, and every valid seal is finalized.
        honest = {m.sender for m in t.prepares} - t.bad
        assert prepared == honest | {batched.committee.node}
        assert len(signers) == n - corrupt >= quorum

    # The device route was taken, and only the PREPREPAREs went to the host.
    device = [r for r in batched.rows if r["route"] == "device"]
    host = [r for r in batched.rows if r["route"] == "host"]
    assert sum(r["dispatches"] for r in host) == HEIGHTS
    (recovers,) = [r for r in device if r["program"] == "ecdsa_recover"]
    # After it a height is TWO recover dispatches: the PREPARE flood, and
    # the COMMIT flood with every COMMIT's seal in it.
    assert recovers["dispatches"] == 2 * HEIGHTS
    assert recovers["padded_lanes"] == 2 * 64 * HEIGHTS
    assert recovers["live_lanes"] == (n - 1 + 2 * n) * HEIGHTS

    # What the drains report is what the generator labelled: per height the
    # PREPREPARE, the PREPARE flood (the node's own among it; every
    # corrupted peer's envelope is mangled), the COMMIT flood (half of them
    # mangle that envelope too), and in the same drain the seal of EVERY
    # COMMIT of the flood (the other half carry a mangled seal; a seal
    # behind a mangled envelope is judged too, and never asked for).
    bad_envelopes = corrupt // 2
    assert _verdicts(batched, "senders") == [
        (1, 0),
        (n - 1, corrupt),
        (n, bad_envelopes),
    ] * HEIGHTS
    assert _verdicts(batched, "seals") == [(n, corrupt - bad_envelopes)] * HEIGHTS
    # The engine's seal drain asked for the seals of the COMMITs that
    # passed, and every one was a verdict the COMMIT flood had left.
    drains = [r[5] for r in batched.records if r[:2] == ("X", "verify.early_exit")]
    assert [(a["route"], a["lanes"], a["cached"]) for a in drains] == [
        ("device", n - bad_envelopes, n - bad_envelopes)
    ] * HEIGHTS
    floods = [
        (a["chunks"], a["lanes"], a["seal_lanes"])
        for r in batched.records
        if r[:2] == ("X", "verify.drain")
        for a in [r[5]]
        if a["route"] == "device"
    ]
    assert floods == [(1, n - 1, 0), (1, n, n)] * HEIGHTS
    assert not _verdicts(reference, "senders")  # no batch verifier, no drain


def test_a_committee_just_over_the_old_ceiling_flushes_each_phase_once():
    """259 validators on the host route: the PREPARE flood is 258 messages
    and the COMMIT flood 259, both over the 256 at which a default ingress
    used to cut.  Three flushes a height and four verifier calls, as
    ``tests/test_span_contract.py`` has it for 4 and 16."""
    n = 259
    st = _run(n, 11, HostBatchVerifier)
    assert st.counts["failed"] == 0 and len(st.samples) == HEIGHTS
    assert st.checked is True, st.info.get("guarantee_broken")
    assert st.counts["flush_sizes"] == [1, n - 1, n] * HEIGHTS
    flushes = [r[5] for r in st.records if r[:2] == ("X", "ingress.flush")]
    assert [(a["lanes"], a["cut"]) for a in flushes] == [
        (1, False),
        (n - 1, False),
        (n, False),
    ] * HEIGHTS
    (row,) = st.rows
    assert (row["program"], row["route"]) == ("ecdsa_recover", "host")
    assert row["dispatches"] == 4 * HEIGHTS
    corrupt = driver.corrupted_peers(n, CORRUPT_SHARE)
    assert corrupt == 77
    assert _verdicts(st, "senders") == [
        (1, 0),
        (n - 1, corrupt),
        (n, corrupt // 2),
    ] * HEIGHTS
    # The host early exit judges in arrival order and stops at quorum.
    for live, rejected in _verdicts(st, "seals"):
        assert live - rejected == st.committee.quorum == 173
        assert live < n - corrupt // 2


def _stand_in_digest(blocks, counts, hz=None):
    """The digest program's stand-in: zero rows, ``hz`` joined behind them."""
    import numpy as np

    n = len(counts)
    zw = np.zeros((n if hz is None else n + len(hz), 8), np.uint32)
    if hz is not None:
        zw[n:] = hz
    return zw


def test_what_pack_does_once_a_drain_at_100_validators(monkeypatch):
    """The headline deployment's round-0 heights on the device route, the
    recover and digest programs replaced by stand-ins (every live lane
    valid, so no peer is corrupted): COUNTS of what the pack layer does per
    message and per drain (ISSUE 36; ``PERF.md`` section 5).  The generic
    encoder runs for the node's own messages and the PREPREPARE (one
    message, host route) and for no peer's PREPARE or COMMIT; the pack
    cache takes one lookup and one store call a flood; the joint layout is
    written in place."""
    import numpy as np

    from go_ibft_tpu.messages.wire import IbftMessage, MessageType
    from go_ibft_tpu.utils import metrics
    from go_ibft_tpu.verify import DeviceBatchVerifier, batch
    from go_ibft_tpu.verify.pipeline import PackCache

    n = 100
    monkeypatch.setattr(
        batch, "_recover_kernel", lambda zw, r, s, v, c, t, live: np.asarray(live)
    )
    monkeypatch.setattr(batch, "_digest_kernel", _stand_in_digest)
    encoded, window = [], []
    encode, measure = IbftMessage.encode, driver.measure

    def counting_encode(self, **kw):
        if window:  # the labels and the oracle encode too, afterwards
            encoded.append((self.sender, self.type))
        return encode(self, **kw)

    def measure_in_window(st, seconds):
        window.append(True)
        try:
            return measure(st, seconds)
        finally:
            window.clear()

    monkeypatch.setattr(IbftMessage, "encode", counting_encode)
    monkeypatch.setattr(driver, "measure", measure_in_window)
    calls = {"lookup_many": [], "store_rows": [], "store": [], "lookup": []}
    for name, sink in calls.items():
        orig = getattr(PackCache, name)

        def counted(
            self, first, *rest, _orig=orig, _sink=sink, _whole=name in ("lookup_many", "store_rows")
        ):
            if window:  # a drain's call is noted with its message count
                _sink.append(len(first) if _whole else 1)
            return _orig(self, first, *rest)

        monkeypatch.setattr(PackCache, name, counted)
    joint = {"packs": 0, "inside": False, "concatenates": 0}
    joint_inputs = DeviceBatchVerifier._joint_inputs
    concatenate = np.concatenate

    def counted_joint(self, sub, riders, lanes):
        joint["packs"] += bool(window)
        joint["inside"] = True
        try:
            return joint_inputs(self, sub, riders, lanes)
        finally:
            joint["inside"] = False

    def counted_concatenate(*a, **kw):
        joint["concatenates"] += joint["inside"]
        return concatenate(*a, **kw)

    monkeypatch.setattr(DeviceBatchVerifier, "_joint_inputs", counted_joint)
    monkeypatch.setattr(np, "concatenate", counted_concatenate)
    generic = metrics.get_counter(batch.PACK_GENERIC_KEY)

    st = _run(n, 36, AdaptiveBatchVerifier, corrupt_share=0.0)
    assert st.counts["failed"] == 0 and len(st.samples) == HEIGHTS
    assert st.counts["flush_sizes"] == [1, n - 1, n] * HEIGHTS
    node = st.committee.node
    # The generic encoder: the node signing (and sending) its own PREPARE
    # and COMMIT, and the peers' PREPREPARE on the host route.  No peer's
    # PREPARE or COMMIT reaches it: 98 + 99 of them a height were packed
    # (the node's own two come back through the ingress, and pack too).
    assert {sender for sender, _ in encoded if sender != node} == {
        t.proposer for t in st.labels
    }
    assert all(
        kind == MessageType.PREPREPARE for sender, kind in encoded if sender != node
    )
    assert metrics.get_counter(batch.PACK_GENERIC_KEY) == generic
    # Three calls a height, where the parent made 3 + 99 + 100.
    assert [(sender == node, int(kind)) for sender, kind in encoded] == [
        (False, MessageType.PREPREPARE),
        (True, MessageType.PREPARE),
        (True, MessageType.COMMIT),
    ] * HEIGHTS
    # The pack cache: per flood ONE lookup call and ONE store call, for the
    # whole drain (99 PREPAREs, 100 COMMITs); never a call per message.
    assert calls["lookup_many"] == calls["store_rows"] == [n - 1, n] * HEIGHTS
    assert calls["store"] == calls["lookup"] == []
    # Both floods of a height pack through the joint layout (256 lanes), and
    # it joins nothing on the host: rows are written where they are sent.
    assert joint == {"packs": 2 * HEIGHTS, "inside": False, "concatenates": 0}
    # The seals pack first: their hash rows ride the envelopes' digest
    # launch, which joins both halves' rows (ISSUE 38).
    packs = [
        (r[5]["kind"], r[5]["lanes"], r[5].get("templated"), r[5].get("joined"))
        for r in st.records
        if r[:2] == ("X", "verify.pack") and "kind" in r[5]
    ]
    assert packs == [
        ("senders", n - 1, n - 1, 128),
        ("seals", n, None, None),
        ("senders", n, n, 128),
    ] * HEIGHTS


@pytest.mark.parametrize("n", [100, 300])
def test_no_flood_of_a_height_waits_out_the_held_back_ceiling(monkeypatch, n):
    """Round-0 heights through the driver's wiring with drains that block
    the loop as a device dispatch does (stand-in programs that sleep past
    ``HELD_BACK_FACTOR`` windows): three flushes a height of 1, n - 1, n,
    and the ``ingress.wait`` of each says why it ended when it did: the
    PREPREPARE ``soon`` (never timed, though it follows the COMMIT drain),
    the PREPARE flood ``soon`` (it follows the PREPREPARE's quick host
    flush), the COMMIT flood ``whole`` (it follows the PREPARE drain and is
    device-sized when its turn ends); none ``held_back`` (ISSUE 41)."""
    import time

    import numpy as np

    from go_ibft_tpu.core import BatchingIngress
    from go_ibft_tpu.verify import batch

    blocked = 1.5 * BatchingIngress.HELD_BACK_FACTOR * 0.002  # the default window

    def recover(zw, r, s, v, c, t, live):
        time.sleep(blocked)
        return np.asarray(live)

    monkeypatch.setattr(batch, "_recover_kernel", recover)
    monkeypatch.setattr(batch, "_digest_kernel", _stand_in_digest)
    st = _run(n, 41, AdaptiveBatchVerifier, corrupt_share=0.0)
    assert st.ingress.max_delay == 0.002 and st.ingress.burst_hint is None
    assert st.counts["failed"] == 0 and len(st.samples) == HEIGHTS
    assert st.counts["flush_sizes"] == [1, n - 1, n] * HEIGHTS
    waits = [r[5] for r in st.records if r[:2] == ("X", "ingress.wait")]
    assert [(a["why"], a["lanes"]) for a in waits] == [
        ("soon", 1),
        ("soon", n - 1),
        ("whole", n),
    ] * HEIGHTS
    device = {r["program"]: r["dispatches"] for r in st.rows if r["route"] == "device"}
    assert device["ecdsa_recover"] == (2 if n == 100 else 3) * HEIGHTS


# -- a drain's rows reach the device through the compiled calls alone ---------


class _HandOver:
    """The verifier's four programs replaced by stand-ins that note each call
    (every live lane valid, zero digest rows with ``hz`` joined behind them;
    the fused programs must not run), and ``jnp.asarray``,
    ``jnp.concatenate`` and ``jax.device_put`` wrapped to note every call
    that code under ``go_ibft_tpu/verify/`` makes on a host array.  Both
    only while ``window`` is non-empty."""

    def __init__(self, monkeypatch):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from go_ibft_tpu.verify import batch

        self.window, self.calls, self.live, self.eager = [True], [], [], []
        asarray = jnp.asarray

        def digest(blocks, counts, hz=None):
            for a in (blocks, counts) + (() if hz is None else (hz,)):
                assert type(a) is np.ndarray  # handed over as packed
            assert (blocks.dtype, counts.dtype) == (np.uint32, np.int32)
            if self.window:
                self.calls.append(("digest", 2 if hz is None else 3, len(counts)))
            zw = _stand_in_digest(blocks, counts, hz)
            return asarray(zw)  # a device array, as the program's rows are

        def recover(zw, r, s, v, claimed, table, live):
            for a in (r, s, v, claimed, live):
                assert type(a) is np.ndarray
            assert not isinstance(table, np.ndarray)  # device-resident
            assert [a.dtype for a in (r, s, v, claimed, live)] == [
                np.int32, np.int32, np.int32, np.uint32, np.bool_
            ]
            assert zw.dtype == np.uint32 and zw.shape == (len(live), 8)
            if self.window:
                self.calls.append(("recover", type(zw) is np.ndarray, len(live)))
                self.live.append(int(live.sum()))
            return live

        monkeypatch.setattr(batch, "_digest_kernel", digest)
        monkeypatch.setattr(batch, "_recover_kernel", recover)

        def host_arrays(x):
            if isinstance(x, (list, tuple)):
                return any(host_arrays(a) for a in x)
            return isinstance(x, np.ndarray)

        def noting(name, orig):
            def wrapped(x, *a, **kw):
                caller = sys._getframe(1).f_code
                where = caller.co_filename.replace(os.sep, "/")
                if self.window and "go_ibft_tpu/verify/" in where and host_arrays(x):
                    self.eager.append((name, caller.co_name))
                return orig(x, *a, **kw)

            return wrapped

        monkeypatch.setattr(jnp, "asarray", noting("asarray", jnp.asarray))
        monkeypatch.setattr(jnp, "concatenate", noting("concatenate", jnp.concatenate))
        monkeypatch.setattr(jax, "device_put", noting("device_put", jax.device_put))


def _dispatch_spans(records):
    """``operands`` of the device route's ``verify.dispatch`` spans and
    ``(kind, joined)`` of the ``verify.pack`` spans, in order."""
    operands = [
        r[5].get("operands")
        for r in records
        if r[:2] == ("X", "verify.dispatch") and r[5].get("route") == "device"
    ]
    packs = [
        (r[5]["kind"], r[5].get("joined"))
        for r in records
        if r[:2] == ("X", "verify.pack") and "kind" in r[5]
    ]
    return operands, packs


@pytest.mark.parametrize(
    "n, calls, operands, packs",
    [
        (  # the joint layout: each flood one digest+join and one recover
            100,
            [("digest", 3, 128), ("recover", False, 256)] * 2,
            [5, 5],
            [("senders", 128), ("seals", None), ("senders", 128)],
        ),
        (  # from the fold width up: the COMMITs' seals are the next chunk
            300,
            [("digest", 2, 512), ("recover", False, 512)] * 2
            + [("recover", True, 512)],
            [5, 5, 6],
            [("senders", None), ("senders", None), ("seals", None)],
        ),
    ],
    ids=["joint-100v", "next-chunk-300v"],
)
def test_a_height_hands_its_rows_to_the_compiled_calls_and_nothing_else(
    monkeypatch, n, calls, operands, packs
):
    """A round-0 height on the device route after its PREPREPARE (one lane,
    on the host): exactly the compiled calls its layout owes, each handed
    the packers' numpy arrays; no eager ``jnp`` call or ``device_put`` on a
    host array anywhere in the verifier (ISSUE 38), the validator table's
    upload included: the set's table went up once, in warm-up, and no height
    of a fixed committee uploads it again (ISSUE 45)."""
    from go_ibft_tpu.utils import metrics
    from go_ibft_tpu.verify import batch

    over = _HandOver(monkeypatch)
    measure = driver.measure

    def measure_in_window(st, seconds):
        over.window.append(True)
        try:
            return measure(st, seconds)
        finally:
            over.window.clear()

    monkeypatch.setattr(driver, "measure", measure_in_window)
    over.window.clear()  # ``warm`` runs a spare height's drains: not counted
    eager_puts = metrics.get_counter(batch.EAGER_PUTS_KEY)
    st = _run(n, 38, AdaptiveBatchVerifier, corrupt_share=0.0)
    assert st.counts["failed"] == 0 and len(st.samples) == HEIGHTS
    assert over.calls == calls * HEIGHTS
    assert over.live == ([n - 1, 2 * n] if n == 100 else [n - 1, n, n]) * HEIGHTS
    assert over.eager == []
    assert metrics.get_counter(batch.EAGER_PUTS_KEY) == eager_puts
    assert _dispatch_spans(st.records) == (operands * HEIGHTS, packs * HEIGHTS)
    by_program = {
        r["program"]: r["dispatches"] for r in st.rows if r["route"] == "device"
    }
    assert by_program == {
        "digest_words": 2 * HEIGHTS,
        "ecdsa_recover": sum(c[0] == "recover" for c in calls) * HEIGHTS,
    }


@pytest.mark.parametrize("case", ["prepare-flood-dead-half", "one-oversize-payload"])
def test_a_drain_hands_its_rows_to_the_compiled_calls_and_nothing_else(
    monkeypatch, case
):
    """One 100-validator drain: a PREPARE flood (the joint layout with a
    dead second half: still the digest+join program, zero hash rows), and a
    COMMIT flood with one payload over the device digest's largest bucket
    (its digest and the join made on the host: a two-operand digest launch,
    still ONE recover launch, every row a host array)."""
    from benchmark.lib.committee import Committee
    from go_ibft_tpu.messages.wire import MessageType, PrePrepareMessage, Proposal
    from go_ibft_tpu.verify import DeviceBatchVerifier, batch

    c = Committee(100, 38)
    t = c.traffic(1, 0)
    over = _HandOver(monkeypatch)
    dev = DeviceBatchVerifier(c.src)
    rec = trace.enable()
    try:
        if case == "prepare-flood-dead-half":
            msgs = t.prepares
            assert dev.verify_senders(msgs).all()
            want, live = [("digest", 3, 128), ("recover", False, 256)], len(msgs)
            spans = ([5], [("senders", 128)])
        else:
            msgs = list(t.commits)
            big = msgs[7]
            big.type, big.commit_data = MessageType.PREPREPARE, None
            big.preprepare_data = PrePrepareMessage(
                proposal=Proposal(
                    raw_proposal=b"\x07" * (batch.MAX_DEVICE_PAYLOAD + 1), round=0
                ),
                proposal_hash=t.phash,
            )
            assert dev.verify_senders(msgs).all()
            want, live = [("digest", 2, 128), ("recover", True, 256)], 2 * len(msgs) - 1
            spans = ([6], [("seals", None), ("senders", None)])
            assert len(dev._seal_verdicts) == len(msgs) - 1
        assert _dispatch_spans(rec.snapshot()) == spans
    finally:
        trace.disable()
    assert over.calls == want
    assert over.live == [live]
    assert over.eager == [("asarray", "_put_table")]
