"""Driver ``round0``: live heights through one engine, round 0 every time.

The system under test is the library's own seam: one ``IBFT`` with a
``BatchingIngress`` and a default-constructed ``AdaptiveBatchVerifier``, as
one validator of the configuration's committee.  The other validators are
pre-signed peers.  Per height the harness submits the proposer's PREPREPARE
to the ingress, answers the node's own PREPARE with every peer's PREPARE at
once and its own COMMIT with every peer's COMMIT at once (message delay 0),
and times from outside the program: ``t0`` is the ``ingress.submit`` of the
PREPREPARE (or, where the node proposes, its own PREPREPARE multicast),
``t1`` the engine's call to ``insert_proposal`` on the backend the harness
owns.
"""

from __future__ import annotations

import asyncio
import gc
import math
import pickle
import time
from types import SimpleNamespace

from benchmark.lib.committee import Committee, GuaranteeBroken, block_bytes
from benchmark.lib.harness import NullLogger, Probe
from benchmark.lib.signing import SigningPool



def corrupted_peers(validators: int, share: float) -> int:
    return math.floor(share * validators)


THROW_AWAY_ROUND_TIMEOUT_S = 300.0  # a cold compile inside it must not end round 0


def throw_away_heights(traffic: dict) -> int:
    """The mix's first signed heights, driven before the timed warm ones.
    None where the mix does not ask for any: ``tests/test_deployment_reference.py``
    drives this driver with a mix of its own and counts its heights."""
    return traffic.get("throw_away_heights", 0)


def presigned_heights(traffic: dict, validators: int, seconds: float) -> int:
    """Heights signed before the window: the throw-away ones, the warm ones,
    and the window's at ``presign_msgs_per_s`` (2 x validators messages a
    height)."""
    return (
        throw_away_heights(traffic)
        + traffic["warm_heights"]
        + math.ceil(seconds * traffic["presign_msgs_per_s"] / (2 * validators))
    )


def _timed_backend(committee: Committee, on_insert):
    """The backend the harness hands the engine: ``ECDSABackend`` with the
    node's key, blocks that are a pure function of the height, and a clock
    read at ``insert_proposal``."""
    from go_ibft_tpu.crypto.backend import ECDSABackend

    class TimedBackend(ECDSABackend):
        def insert_proposal(self, proposal, committed_seals) -> None:
            on_insert(time.perf_counter())
            super().insert_proposal(proposal, committed_seals)

    return TimedBackend(
        committee.node_key,
        committee.src,
        build_proposal_fn=lambda view: block_bytes(view.height),
    )


class _Wire:
    """The engine's transport.  It delivers the node's own messages back
    through the ingress, like everyone else's, and plays the peers: their
    PREPAREs follow the node's PREPARE (or its own PREPREPARE), their
    COMMITs its COMMIT, in the same turn of the event loop."""

    def __init__(self, st) -> None:
        self.st = st

    def multicast(self, message) -> None:
        from go_ibft_tpu.messages.wire import MessageType

        st = self.st
        t = st.current
        st.ingress.submit(message)
        if t is None or message.view.height != t.height or message.view.round:
            return
        if message.type == MessageType.PREPREPARE:
            st.t0 = time.perf_counter()
            st.probe.phase("prepare_flood")
            peers = t.prepares
        elif message.type == MessageType.PREPARE:
            st.probe.phase("prepare_flood")
            peers = t.prepares
        elif message.type == MessageType.COMMIT:
            st.probe.phase("commit_flood")
            peers = t.commits
        else:
            return
        for m in peers:
            st.ingress.submit(m)


def setup(config: dict, traffic: dict, seed: int, seconds: float):
    from go_ibft_tpu.core import IBFT, BatchingIngress
    from go_ibft_tpu.utils import calibration
    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache
    from go_ibft_tpu.verify import AdaptiveBatchVerifier

    n = config["validators"]
    st = SimpleNamespace()
    st.config, st.traffic, st.seed = config, traffic, seed
    st.probe = Probe()
    st.committee = Committee(n, seed)  # builds the native library once
    st.corrupt = corrupted_peers(n, traffic["corrupt_share"])
    st.heights = presigned_heights(traffic, n, seconds)
    st.pool = SigningPool()
    st.pool.submit_flood(n, seed, range(1, st.heights + 1), st.corrupt)

    st.cache_dir = enable_persistent_cache()
    st.verifier = AdaptiveBatchVerifier(st.committee.src)
    st.info = {
        "cutover": st.verifier.cutover,
        "cutover_source": (
            "default"
            if calibration.measured_cutover() is None
            else "calibration.json"
        ),
        "corrupted_peers_per_height": st.corrupt,
        "presigned_heights": st.heights,
        "signing_workers": st.pool.workers,
        "cache_dir": st.cache_dir,
    }

    st.current = None
    st.t0 = st.t1 = None
    st.flushes = []
    st.locked = {}
    st.finalized = []  # (labels, proposal, seals)
    st.blobs = []
    st.next = 0

    def on_insert(now: float) -> None:
        st.t1 = now
        st.probe.phase("between_heights")

    st.backend = _timed_backend(st.committee, on_insert)
    st.engine = IBFT(
        NullLogger(), st.backend, _Wire(st), batch_verifier=st.verifier
    )
    st.engine.set_base_round_timeout(float(config["base_round_timeout_s"]))

    def add_messages(batch) -> None:
        st.flushes.append(len(batch))
        st.engine.add_messages(batch)

    st.ingress = BatchingIngress(add_messages)
    st.engine.on_lock = lambda h, r, cert, proposal: st.locked.setdefault(
        h, [m.sender for m in cert.prepare_messages]
    )
    st.loop = asyncio.new_event_loop()
    return st


def warm(st) -> None:
    """Load the cell's shapes through the verifier's public methods, on a
    spare signed height; then the mix's throw-away heights through the
    ingress and the engine under a round timeout no compile outlasts (a shape
    that only a live height reaches compiles HERE, not inside a height that
    has 10 s to finalize in round 0); then a few unmeasured, timed heights."""
    from go_ibft_tpu.messages.helpers import CommittedSeal

    c = st.committee
    spare = c.traffic(st.heights + 1, st.corrupt)
    st.verifier.verify_senders(spare.prepares)
    st.verifier.verify_senders(spare.commits)
    seals = [
        CommittedSeal(signer=m.sender, signature=m.commit_data.committed_seal)
        for m in spare.commits
    ]
    st.verifier.verify_seals_early_exit(
        spare.phash, seals, spare.height, threshold=c.quorum
    )
    st.verifier.verify_committed_seals(spare.phash, seals, spare.height)

    jobs, native = st.pool.collect()
    st.pool.close()
    st.info["native_signer"] = bool(native and c.native)
    st.blobs = [blob for blobs in jobs for blob in blobs]
    throw_away = throw_away_heights(st.traffic)
    if throw_away:
        st.engine.set_base_round_timeout(THROW_AWAY_ROUND_TIMEOUT_S)
        st.loop.run_until_complete(_drive(st, math.inf, throw_away))
        st.engine.set_base_round_timeout(float(st.config["base_round_timeout_s"]))
        st.info["throw_away_heights_ms"] = [round(s, 3) for s in st.samples]
        st.info["throw_away_failed"] = st.failed
    warm_n = st.traffic["warm_heights"]
    st.loop.run_until_complete(_drive(st, math.inf, warm_n))
    st.info["warm_heights_ms"] = [round(s, 3) for s in st.samples]
    del st.finalized[:]
    gc.collect()
    gc.freeze()


async def _drive(st, seconds: float, max_heights: float) -> None:
    timeout = 3 * st.engine.base_round_timeout
    st.samples, st.spans, st.attempted, st.failed = [], [], 0, 0
    st.ran_out = False
    del st.flushes[:]
    st.probe.phase("between_heights")
    start = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - start
        st.probe.boundary(elapsed, len(st.blobs) - st.next)
        if st.attempted >= max_heights or (
            elapsed >= seconds and not st.probe.wants_more()
        ):
            break
        if st.next >= len(st.blobs):
            st.ran_out = True
            break
        t = pickle.loads(st.blobs[st.next])
        st.blobs[st.next] = None
        st.next += 1
        st.attempted += 1
        st.current, st.t0, st.t1 = t, None, None
        before = len(st.backend.inserted)
        sequence = asyncio.ensure_future(st.engine.run_sequence(t.height))
        await asyncio.sleep(0)
        if t.preprepare is not None:
            st.probe.phase("proposal")
            st.t0 = time.perf_counter()
            st.ingress.submit(t.preprepare)
        try:
            await asyncio.wait_for(sequence, timeout)
        except asyncio.TimeoutError:
            st.failed += 1
            st.probe.phase("between_heights")
            break
        st.current = None
        inserted = st.backend.inserted[before:]
        if len(inserted) != 1 or inserted[0][0].round != 0 or st.t0 is None:
            st.failed += 1
            continue
        st.samples.append((st.t1 - st.t0) * 1e3)
        st.spans.append((st.t0, st.t1))
        t.preprepare, t.prepares, t.commits = None, [], []  # keep the labels
        st.finalized.append((t, inserted[0][0], inserted[0][1]))
    st.elapsed_s = time.perf_counter() - start
    st.probe.phase(None)


def measure(st, seconds: float):
    st.loop.run_until_complete(_drive(st, seconds, math.inf))
    counts = {
        "attempted": st.attempted,
        "failed": st.failed,
        "operations": len(st.samples),
        "elapsed_s": st.elapsed_s,
        "ran_out_of_presigned": st.ran_out,
        "flush_sizes": list(st.flushes),
        "op_spans": list(st.spans),
    }
    return list(st.samples), counts


def check(st) -> bool:
    """Every finalized height of the window against the labels; a seeded
    sample of them also against the sequential host oracle."""
    from go_ibft_tpu.verify import HostBatchVerifier

    st.engine.messages.close()
    st.loop.close()
    oracle = HostBatchVerifier(st.committee.src)
    sample = set(range(0, len(st.finalized), max(1, len(st.finalized) // 16)))
    broken = st.info["compared"] = {"heights_against_their_labels": {"value": 1, "limit": 0}}
    try:
        for i, (t, proposal, seals) in enumerate(st.finalized):
            st.committee.check_finalized(
                t, proposal, seals, oracle=oracle if i in sample else None
            )
            senders = st.locked.get(t.height)
            if senders is None:
                raise GuaranteeBroken(f"height {t.height}: no prepare quorum locked")
            if set(senders) & t.bad:
                raise GuaranteeBroken(
                    f"height {t.height}: a corrupted PREPARE was accepted"
                )
    except GuaranteeBroken as err:
        st.info["guarantee_broken"] = str(err)
        return False
    st.info["heights_checked"] = len(st.finalized)
    st.info["heights_oracle_checked"] = len(sample) if st.finalized else 0
    broken["heights_against_their_labels"]["value"] = 0
    return bool(st.finalized)
