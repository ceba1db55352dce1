"""Driver ``node``: live heights through the deployed validator, over sockets.

The system under test is what ``python -m go_ibft_tpu.node --config
node.toml`` starts: a ``ValidatorNode`` built from a ``NodeConfig`` that was
written as a ``node.toml`` and read back by ``load_config``, embedded in the
harness process (``install_signal_handlers=False``) so that the compile
counter, the cost ledger, the memory peak and the profiler read the process
that holds the chip.  Its engine's verifier is a ``TenantScheduler`` handle,
its transport a ``GrpcTransport`` on TCP, its chain a ``ChainRunner`` with a
``WriteAheadLog`` in a fresh ``data_dir`` under ``<checkout>/.cache``.  The
other validators are pre-signed peers in a few spawned processes
(``benchmark/lib/peer_hosts.py``), one listener and one channel each.

A height: the driver tells the proposer's host to send the PREPREPARE; every
peer answers the node's PREPARE with its own and the node's COMMIT with its
own, from its own listener; the next PREPREPARE goes out when the driver has
seen the height final (closed loop, block time 0).  One height in ``n`` the
node proposes itself, as soon as the height before is final, and needs no
driver: such a height is timed like the others, but the driver marks no
operation boundary before it (something is in flight).

The sample, on the harness process's clock: ``t0`` is the PREPREPARE's
``ingress.submit`` inside the node (its own PREPREPARE's, within its
multicast, where it proposes); ``t1`` is the return of the runner's
``on_finalize``: after the backend's ``insert_proposal`` and after
``append_finalize`` has fsynced.

What the harness touches of the built node, all after construction and none
of it an option of the program: ``transport._deliver`` is wrapped (the clock
at ``t0`` and the phase marks), ``engine.on_lock`` and ``engine.on_finalize``
are wrapped (the labels' side of ``check``, the clock at ``t1``), and the
backend's block builder is the committee's (``Backend.BuildProposal`` is the
embedder's: the peers' votes were signed over ``benchmark block <height>``).
"""

from __future__ import annotations

import asyncio
import gc
import json
import math
import os
import pickle
import shutil
import time
from types import SimpleNamespace

from benchmark.drivers.round0 import (
    corrupted_peers,
    presigned_heights,
    throw_away_heights,
)
from benchmark.lib.committee import Committee, GuaranteeBroken, block_bytes
from benchmark.lib.harness import Probe
from benchmark.lib.peer_hosts import PeerHosts, median_hop_ms
from benchmark.lib.signing import SigningPool

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BOOT_TIMEOUT_S = 900.0  # a cold boot compiles the committee's ladder programs


MEMORY_FILESYSTEMS = ("tmpfs", "ramfs")


def mount_of(path: str, mounts: str = "/proc/mounts") -> tuple:
    """``(mount point, filesystem type, options)`` of the mount ``path``
    lies on, by the table ``mounts``; ``("", "unknown", "")`` without one."""
    best = ("", "unknown", "")
    try:
        with open(mounts) as fh:
            for line in fh:
                _dev, mount, fstype, options = line.split()[:4]
                under = path == mount or path.startswith(mount.rstrip("/") + "/")
                if under and len(mount) >= len(best[0]):  # the later mount is on top
                    best = (mount, fstype, options)
    except (OSError, ValueError):
        pass
    return best


def memory_backed(path: str, mounts: str = "/proc/mounts") -> bool:
    """Whether an fsync under ``path`` reaches no disk: a ``tmpfs`` or a
    ``ramfs``, or an overlay whose upper layer (where writes land) lies on
    one, as far as this mount table shows it."""
    mount, fstype, options = mount_of(path, mounts)
    if fstype == "overlay":
        upper = [o[len("upperdir="):] for o in options.split(",") if o.startswith("upperdir=")]
        under = mount_of(upper[0], mounts) if upper else (mount,)
        # An upper layer outside this mount namespace resolves to the
        # overlay itself: it cannot be told, and is not refused.
        return under[0] != mount and under[1] in MEMORY_FILESYSTEMS
    return fstype in MEMORY_FILESYSTEMS


def node_config(st, ports: dict):
    """The node's ``node.toml``, written and read back as the binary reads
    it.  Nothing here that a ``node.toml`` cannot say."""
    from go_ibft_tpu.node.config import (
        ConsensusConfig,
        NodeConfig,
        ProofApiConfig,
        TelemetryConfig,
        TraceConfig,
        load_config,
    )
    from go_ibft_tpu.obs import trace as obs_trace

    dep = st.config["node_toml"]
    committee = st.committee
    written = NodeConfig(
        node_id=0,
        key_seed=committee.seeds[0],
        data_dir=st.data_dir,
        validators={a.hex(): p for a, p in committee.powers.items()},
        heights=0,
        consensus=ConsensusConfig(
            listen=dep["consensus"]["listen"],
            peers={f"v{i}": f"127.0.0.1:{ports[i]}" for i in sorted(ports)},
            base_round_timeout_s=float(st.config["base_round_timeout_s"]),
        ),
        proof_api=ProofApiConfig(listen=dep["proof_api"]["listen"]),
        telemetry=TelemetryConfig(listen=dep["telemetry"]["listen"]),
        # [trace] follows --trace: the harness has the ring on by now, and
        # the node's ring is as long as the harness's.
        trace=TraceConfig(enabled=obs_trace.enabled(), ring=1 << 20),
        sched_enabled=dep["sched"]["enabled"],
        sched_route=dep["sched"]["route"],
    )
    path = os.path.join(st.data_dir, "node.toml")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(written.to_toml())
    return load_config(path)


def _transport_counters() -> dict:
    from go_ibft_tpu.net import grpc_transport as wire
    from go_ibft_tpu.utils import metrics

    return {
        "rpcs_in": metrics.get_counter(wire.RPCS_IN_KEY),
        "send_failures": metrics.get_counter(wire.SEND_FAILURE_KEY),
        "peer_reconnects": metrics.get_counter(wire.PEER_RECONNECT_KEY),
    }


def setup(config: dict, traffic: dict, seed: int, seconds: float):
    n = config["validators"]
    st = SimpleNamespace()
    st.config, st.traffic, st.seed = config, traffic, seed
    st.probe = Probe()
    # Process-wide and never reset, so read before.  Also: a program older
    # than the node this driver embeds has no ``rpcs_in`` counter, and fails
    # HERE, at once, before any process is started.
    st.counters_before = _transport_counters()
    st.committee = Committee(n, seed)  # builds the native library once
    st.corrupt = corrupted_peers(n, traffic["corrupt_share"])
    st.heights = presigned_heights(traffic, n, seconds)
    st.pool = SigningPool()
    st.pool.submit_flood(n, seed, range(1, st.heights + 1), st.corrupt)
    st.hosts = PeerHosts(
        n, seed, config["assumed"]["peer_hosts"], traffic.get("silent_peers", 0)
    )

    # A fresh data_dir on the machine's disk, inside the checkout.
    st.data_dir = os.path.join(
        ROOT, ".cache", "benchmark_node", f"{seed}-{os.getpid()}"
    )
    shutil.rmtree(st.data_dir, ignore_errors=True)
    os.makedirs(st.data_dir)

    st.index_of = {k.address: i for i, k in enumerate(st.committee.keys)}
    st.t0_of, st.t1_of, st.final = {}, {}, {}
    st.locked, st.inserted = {}, {}
    st.labels, st.arrived_at, st.unmeasured = {}, {}, []
    st.finalized = []  # (labels, proposal, seals) of the window's heights
    st.blobs, st.next = [], 0
    st.node = st.node_task = None
    st.loop = asyncio.new_event_loop()
    st.info = {
        "corrupted_peers_per_height": st.corrupt,
        "presigned_heights": st.heights,
        "signing_workers": st.pool.workers,
        "peer_hosts": [len(s) for s in st.hosts.shares],
        "data_dir": os.path.relpath(st.data_dir, ROOT),
        "data_dir_filesystem": mount_of(st.data_dir)[1],
    }
    return st


def _instrument(st) -> None:
    """The harness's three wraps and the committee's block builder (the
    module docstring says why each)."""
    from go_ibft_tpu.messages.wire import MessageType

    node, me = st.node, st.committee.node
    node.engine.backend._build_proposal_fn = lambda view: block_bytes(view.height)
    submit = node.transport._deliver

    def deliver(message) -> None:
        view = message.view
        if view is not None and not view.round:
            if message.type == MessageType.PREPREPARE:
                st.t0_of.setdefault(view.height, time.perf_counter())
                if message.sender == me:
                    st.probe.phase("prepare_flood")
            elif message.sender == me:
                if message.type == MessageType.PREPARE:
                    st.probe.phase("prepare_flood")
                elif message.type == MessageType.COMMIT:
                    st.probe.phase("commit_flood")
        submit(message)

    node.transport._deliver = deliver
    on_lock, on_finalize = node.engine.on_lock, node.engine.on_finalize

    def locked(height, round_, certificate, proposal) -> None:
        on_lock(height, round_, certificate, proposal)
        st.locked.setdefault(
            height, (round_, [m.sender for m in certificate.prepare_messages])
        )

    def finalized(height, proposal, seals) -> None:
        on_finalize(height, proposal, seals)  # the WAL's fsynced append
        st.t1_of[height] = time.perf_counter()
        st.probe.phase("between_heights")
        st.inserted[height] = (proposal, list(seals))
        waiter = st.final.get(height)
        if waiter is not None and not waiter.done():
            waiter.set_result(None)

    node.engine.on_lock, node.engine.on_finalize = locked, finalized


async def _boot(st) -> None:
    """Start the peers' listeners, build the node from its ``node.toml``,
    let it boot as the binary boots (scheduler warm-up, transport, recovery
    from an empty WAL), and give the peers its address."""
    from go_ibft_tpu.node import ValidatorNode
    from go_ibft_tpu.utils import calibration
    from go_ibft_tpu.utils.jaxcache import resolve_cache_dir

    ports = st.hosts.listen()
    cfg = node_config(st, ports)
    st.node = ValidatorNode(cfg, install_signal_handlers=False)
    _instrument(st)
    loader = asyncio.get_running_loop().run_in_executor(None, _load_traffic, st)
    t_boot = time.perf_counter()
    st.node_task = asyncio.ensure_future(st.node.run())
    deadline = t_boot + BOOT_TIMEOUT_S
    # run() names its height loop's task "node-chain" once the boot is done.
    while not any(t.get_name() == "node-chain" for t in asyncio.all_tasks()):
        if st.node_task.done():
            st.node_task.result()
            raise RuntimeError("the node's run() returned during its boot")
        if time.perf_counter() > deadline:
            raise RuntimeError(f"the node did not boot in {BOOT_TIMEOUT_S:.0f}s")
        await asyncio.sleep(0.01)
    dispatcher = st.node.scheduler.stats()["dispatcher"]
    st.info.update(
        node_boot_s=round(time.perf_counter() - t_boot, 3),
        cutover=dispatcher["cutover"],
        cutover_source=(
            "default" if calibration.measured_cutover() is None else "calibration.json"
        ),
        sched_route=dispatcher["route"],
        recover_widths_loaded=dispatcher["widths"],
        cache_dir=resolve_cache_dir(),
    )
    t_booted = time.perf_counter()
    await loader
    st.info["waited_for_signed_traffic_s"] = round(time.perf_counter() - t_booted, 3)
    st.info["peer_channels"] = st.hosts.connect(
        f"127.0.0.1:{st.node.transport.bound_port}"
    )


def _load_traffic(st) -> None:
    """Wait for the signed heights and hand them to the peer hosts (on a
    thread of its own: the node's boot holds the loop's meanwhile)."""
    jobs, native = st.pool.collect()
    st.pool.close()
    st.info["native_signer"] = bool(native and st.committee.native)
    st.blobs = [blob for blobs in jobs for blob in blobs]
    st.info["peer_messages_loaded"] = st.hosts.load(st.blobs)


def warm(st) -> None:
    """The node's own boot while the peers get their signed messages, then
    the mix's throw-away heights and a few unmeasured, timed ones through
    the sockets.  Once booted the node is in height 1, round 0, and its round
    timer runs: the first PREPREPARE has to follow within the round timeout."""
    st.loop.run_until_complete(_boot(st))
    throw_away = throw_away_heights(st.traffic)
    if throw_away:
        st.loop.run_until_complete(_drive(st, math.inf, throw_away))
        st.info["throw_away_heights_ms"] = [round(s, 3) for s in st.samples]
        st.info["throw_away_failed"] = st.failed
    st.loop.run_until_complete(_drive(st, math.inf, st.traffic["warm_heights"]))
    st.info["warm_heights_ms"] = [round(s, 3) for s in st.samples]
    st.info["warm_failed"] = st.failed
    del st.finalized[:]
    gc.collect()
    gc.freeze()


def _labels(st, index: int):
    """The labels of the ``index``-th signed height (its messages are the
    peer hosts' by now)."""
    t = st.labels.get(index)
    if t is None:
        t = pickle.loads(st.blobs[index])
        st.blobs[index] = None
        proposes = t.preprepare is None
        t.preprepare, t.prepares, t.commits = None, [], []
        t.node_proposes = proposes
        st.labels[index] = t
    return t


async def _final(st, t, timeout: float) -> bool:
    """Wait until the node has finalized ``t.height``; whether it did, in
    round 0, with its clocks read."""
    h = t.height
    if h not in st.t1_of:
        waiter = st.final[h] = st.loop.create_future()
        try:
            await asyncio.wait_for(waiter, timeout)
        except asyncio.TimeoutError:
            return False
        finally:
            st.final.pop(h, None)
    proposal, _seals = st.inserted[h]
    return proposal.round == 0 and h in st.t0_of


async def _drive(st, seconds: float, max_heights: float) -> None:
    timeout = 3 * float(st.config["base_round_timeout_s"])
    st.samples, st.spans, st.attempted, st.failed = [], [], 0, 0
    st.ran_out = False
    st.probe.phase("between_heights")
    # A height the node proposed by itself while nobody was measuring (it
    # starts the instant the height before is final) is none of this call's.
    while st.next < len(st.blobs) and _labels(st, st.next).node_proposes:
        t = _labels(st, st.next)
        st.next += 1
        if not await _final(st, t, timeout):
            st.failed += 1
        st.unmeasured.append(t.height)
    start = time.perf_counter()
    while True:
        if st.next >= len(st.blobs):
            st.ran_out = True
            break
        t = _labels(st, st.next)
        if not t.node_proposes:
            # Nothing is in flight: an operation boundary, and the only
            # place the loop may end.
            elapsed = time.perf_counter() - start
            st.probe.boundary(elapsed, len(st.blobs) - st.next)
            if st.attempted >= max_heights or (
                elapsed >= seconds and not st.probe.wants_more()
            ):
                break
            st.probe.phase("proposal")
            st.hosts.preprepare(t.height, st.index_of[t.proposer])
        st.next += 1
        st.attempted += 1
        if not await _final(st, t, timeout):
            st.failed += 1
            st.probe.phase("between_heights")
            if t.height not in st.t1_of:
                break  # the node is stuck in this height: nothing follows
            continue
        t0, t1 = st.t0_of.pop(t.height), st.t1_of[t.height]
        st.samples.append((t1 - t0) * 1e3)
        st.spans.append((t0, t1))
        st.arrived_at[t.height] = t0
        st.finalized.append((t, *st.inserted.pop(t.height)))
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
        "op_spans": list(st.spans),
    }
    return list(st.samples), counts


async def _stop(st) -> dict:
    """The peers' side of the record, then the drain a SIGTERM asks for."""
    # Each host waits out its sends in flight, which the node's loop serves.
    reports = await asyncio.get_running_loop().run_in_executor(None, st.hosts.report)
    await asyncio.sleep(0.05)  # the node's handlers of the last of them
    st.node.request_stop()
    drain = await asyncio.wait_for(st.node_task, 60.0)
    return {"hosts": reports, "drain": drain}


def wal_read_back(st, broken: dict) -> None:
    """The log, re-opened and replayed from disk as ``recover()`` replays
    it, against what the window finalized: every height there, in order,
    with the same block and the same seals, nothing torn; and before each
    height's ``finalize`` record its round-0 ``lock`` record, whose PREPARE
    senders are those the engine locked on."""
    from go_ibft_tpu.chain.wal import WriteAheadLog
    from go_ibft_tpu.messages.wire import PreparedCertificate

    path = st.node.wal_path
    state = WriteAheadLog(path).replay()
    broken["wal_records_torn"]["value"] = int(state.dropped_tail)
    replayed = {b.height: b for b in state.blocks}
    heights = [b.height for b in state.blocks]
    off = 0 if heights == sorted(set(heights)) else 1
    locks, finalize_at = {}, {}
    with open(path, "rb") as fh:
        for i, line in enumerate(fh.read().splitlines()):
            record = json.loads(line)
            if record["kind"] == "lock":
                locks.setdefault(record["height"], (i, record))
            elif record["kind"] == "finalize":
                finalize_at.setdefault(record["height"], i)
    missing = 0
    for t, proposal, seals in st.finalized:
        block = replayed.get(t.height)
        same = (
            block is not None
            and block.proposal.encode() == proposal.encode()
            and [(s.signer, s.signature) for s in block.seals]
            == [(s.signer, s.signature) for s in seals]
        )
        off += 0 if same else 1
        at, record = locks.get(t.height, (None, None))
        held = st.locked.get(t.height)
        if record is None or held is None or at > finalize_at.get(t.height, -1):
            missing += 1
            continue
        pc = PreparedCertificate.decode(bytes.fromhex(record["pc"]))
        senders = [m.sender for m in pc.prepare_messages]
        if (record["round"], senders) != held:
            missing += 1
    broken["wal_heights_off_the_window"]["value"] = off
    broken["wal_lock_records_missing"]["value"] = missing
    st.info["wal"] = {
        "bytes": os.path.getsize(path),
        "blocks_replayed": len(state.blocks),
        "lock_records": len(locks),
    }


def check(st) -> bool:
    """Every finalized height of the window against the labels, a seeded
    sixteenth of them also against the sequential host oracle; the WAL read
    back from disk, and that disk no memory filesystem; the transport's and
    the peers' own counts."""
    from go_ibft_tpu.verify import HostBatchVerifier

    names = (
        "data_dir_in_memory",
        "heights_against_their_labels",
        "wal_heights_off_the_window",
        "wal_lock_records_missing",
        "wal_records_torn",
        "send_failures",
        "peer_reconnects",
        "peer_send_errors",
        "rpcs_in_off_the_peers_sends",
        "peer_hosts_with_jax",
    )
    broken = st.info["compared"] = {k: {"value": 1, "limit": 0} for k in names}
    try:
        stopped = st.loop.run_until_complete(_stop(st))
    finally:
        st.hosts.close()
        st.loop.close()
    hosts, drain = stopped["hosts"], stopped["drain"]
    moved = {k: v - st.counters_before[k] for k, v in _transport_counters().items()}
    sent_at = {}
    for report in hosts:
        sent_at.update(report["preprepare_sent_at"])
    acked = sum(r["acked"] for r in hosts)
    st.info.update(
        preprepare_hop_ms_p50=median_hop_ms(sent_at, st.arrived_at),
        peer_sends={k: sum(r[k] for r in hosts) for k in ("sent", "acked", "heard")},
        rpcs_in=moved["rpcs_in"],
        chain_height=drain["chain_height"],
        heights_not_measured=st.unmeasured,
        sched=drain["sched"] and {
            k: drain["sched"][k]
            for k in ("dispatches", "coalesced_requests", "flush_faults", "served")
        },
    )
    broken["send_failures"]["value"] = moved["send_failures"]
    broken["peer_reconnects"]["value"] = moved["peer_reconnects"]
    broken["peer_send_errors"]["value"] = sum(r["send_errors"] for r in hosts)
    broken["rpcs_in_off_the_peers_sends"]["value"] = abs(st.info["rpcs_in"] - acked)
    broken["peer_hosts_with_jax"]["value"] = sum(1 for r in hosts if r["jax"])
    # The deployment's WAL is on a disk: an fsync into memory is another one.
    broken["data_dir_in_memory"]["value"] = int(memory_backed(st.data_dir))
    wal_read_back(st, broken)

    oracle = HostBatchVerifier(st.committee.src)
    sample = set(range(0, len(st.finalized), max(1, len(st.finalized) // 16)))
    try:
        for i, (t, proposal, seals) in enumerate(st.finalized):
            st.committee.check_finalized(
                t, proposal, seals, oracle=oracle if i in sample else None
            )
            held = st.locked.get(t.height)
            if held is None or held[0] != 0:
                raise GuaranteeBroken(f"height {t.height}: no round-0 prepare quorum locked")
            if set(held[1]) & t.bad:
                raise GuaranteeBroken(
                    f"height {t.height}: a corrupted PREPARE was accepted"
                )
    except GuaranteeBroken as err:
        st.info["guarantee_broken"] = str(err)
        return False
    st.info["heights_checked"] = len(st.finalized)
    st.info["heights_oracle_checked"] = len(sample) if st.finalized else 0
    broken["heights_against_their_labels"]["value"] = 0
    return bool(st.finalized) and not any(c["value"] for c in broken.values())
