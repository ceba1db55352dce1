"""Minimal complete embedder: a 4-validator chain finalizing 3 blocks.

What a user of the reference wires through Backend/Transport/Logger
(go-ibft README "Usage" + core/backend.go:69-85), done with this
framework's batteries included:

* keys + signing + verification  -> crypto.ECDSABackend
* network                        -> core.LoopbackTransport (swap for
                                    net.GrpcTransport across hosts, or
                                    net.IciLockstepTransport on a mesh)
* the TPU data plane             -> verify.DeviceBatchVerifier (optional;
                                    the engine runs the sequential host
                                    path without it)

Run: ``python examples/minimal_embedder.py [--device]``
"""

import argparse
import asyncio
import sys
from typing import Optional

sys.path.insert(0, ".")

# noqa-justified: sys.path setup must precede package imports when run as a
# script from the repo root.
from go_ibft_tpu.core import IBFT, LoopbackTransport  # noqa: E402
from go_ibft_tpu.crypto import PrivateKey  # noqa: E402
from go_ibft_tpu.crypto.backend import ECDSABackend  # noqa: E402


class StdoutLogger:
    def info(self, msg, *args):
        print(f"[info ] {msg} {args if args else ''}")

    def debug(self, msg, *args):
        pass

    def error(self, msg, *args):
        print(f"[error] {msg} {args if args else ''}")


def build_cluster(
    n: int,
    use_device: bool,
    use_bls: bool = False,
    use_mesh: bool = False,
    use_aggregate: bool = False,
    use_speculate: bool = False,
    commit_next_set: bool = False,
):
    # 1. Validator identities and the (static) voting-power map.
    keys = [PrivateKey.from_seed(b"example-validator-%d" % i) for i in range(n)]
    powers = {k.address: 1 for k in keys}
    validators = ECDSABackend.static_validators(powers)

    use_bls = use_bls or use_aggregate
    certifier = hub = None
    if use_bls:
        # BLS committed seals: ECDSA envelopes + BLS G2 seals, so a whole
        # COMMIT quorum certifies with ONE pairing (aggregate verification).
        from go_ibft_tpu.crypto import bls as hbls
        from go_ibft_tpu.crypto.bls_backend import HybridBLSBackend

        bls_keys = [
            hbls.BLSPrivateKey.from_seed(b"example-bls-%d" % i) for i in range(n)
        ]
        if use_aggregate:
            # Production posture: pubkeys enter the aggregation set ONLY
            # with a proof of possession (rogue-key defense), and COMMIT
            # dissemination rides the aggregation tree — seals merge
            # upward as partial aggregates, one O(1) quorum certificate
            # broadcasts down, every node finalizes with ONE pairing.
            from go_ibft_tpu.crypto.quorum_cert import (
                BLSCertifier,
                BLSKeyRegistry,
            )
            from go_ibft_tpu.net import AggregationTreeGossip

            registry = BLSKeyRegistry()
            for k, bk in zip(keys, bls_keys):
                registry.register_key(k.address, bk)
            bls_src = registry
            certifier = BLSCertifier(validators, registry)
            hub = AggregationTreeGossip(certifier, fan_in=2)
        else:
            pubkeys = {
                k.address: bk.pubkey for k, bk in zip(keys, bls_keys)
            }
            bls_src = ECDSABackend.static_validators(pubkeys)

    # 2. One engine per validator, all wired to one loopback "network"
    # (or the aggregation tree in --aggregate mode).
    transport = LoopbackTransport() if hub is None else None
    engines = []
    for i, key in enumerate(keys):
        build = lambda view: b"example block %d" % view.height  # noqa: E731
        if use_bls:
            backend = HybridBLSBackend(
                key, bls_keys[i], validators, bls_src, build_proposal_fn=build
            )
        else:
            # The embedder's block builder: anything bytes. A real chain
            # would assemble transactions here (reference Backend.BuildProposal).
            backend = ECDSABackend(
                key,
                validators,
                build_proposal_fn=build,
                commit_next_set=commit_next_set,
            )
        batch_verifier = None
        if use_mesh:
            # Production scale-out posture: the adaptive router with the
            # sharded mesh rung on top — tiny drains stay on host, large
            # ones on one device, drains past the mesh cutover shard
            # lane-parallel across every visible device (forced host
            # devices work too: XLA_FLAGS=--xla_force_host_platform_
            # device_count=8).  Degrades transparently to the plain
            # device ladder on a 1-device host.  The engine's phase
            # drains AND (in --chain mode) the overlap/sync drains all
            # route through the same ladder.
            from go_ibft_tpu.verify import (
                AdaptiveBatchVerifier,
                MeshBatchVerifier,
            )

            mesh_verifier = MeshBatchVerifier(validators)
            batch_verifier = AdaptiveBatchVerifier(
                validators,
                mesh=mesh_verifier if mesh_verifier.sharded else None,
            )
            batch_verifier.warmup()
        elif use_device:
            from go_ibft_tpu.verify import DeviceBatchVerifier

            batch_verifier = DeviceBatchVerifier(validators)
            batch_verifier.warmup()  # node startup: never compile mid-round
            if use_bls:
                # An explicit batch_verifier overrides the backend's seal
                # path, and BLS seals are 192 bytes — the ECDSA device
                # verifier would reject every one.  Compose instead: device
                # ECDSA for sender envelopes, BLS aggregate for seals.
                from go_ibft_tpu.crypto.bls_backend import HybridBatchVerifier
                from go_ibft_tpu.verify.bls import BLSAggregateVerifier

                batch_verifier = HybridBatchVerifier(
                    batch_verifier, BLSAggregateVerifier(bls_src)
                )
        speculator = None
        if use_speculate:
            # Commit-critical-path posture (ISSUE 9): COMMIT seals
            # arriving ahead of their phase verify off the event loop
            # through the engine's own verifier, and the commit drain
            # early-exits at quorum (on by default), deferring the
            # remainder to the same worker.
            from go_ibft_tpu.verify import HostBatchVerifier as _HBV
            from go_ibft_tpu.verify import SpeculativeVerifier

            speculator = SpeculativeVerifier(
                batch_verifier
                if batch_verifier is not None
                else _HBV(validators)
            )
        engine = IBFT(
            StdoutLogger(),
            backend,
            transport,
            batch_verifier=batch_verifier,
            cert_verifier=certifier,
            speculator=speculator,
        )
        engine.set_base_round_timeout(10.0)
        if hub is not None:
            engine.transport = hub.register(
                key.address, engine.add_message, engine.add_quorum_certificate
            )
        else:
            transport.register(engine.add_message)
        engines.append(engine)
    return engines, certifier, hub


async def main_async(
    n: int,
    heights: int,
    use_device: bool,
    use_bls: bool = False,
    use_mesh: bool = False,
    use_aggregate: bool = False,
    use_speculate: bool = False,
) -> None:
    engines, _certifier, hub = build_cluster(
        n, use_device, use_bls, use_mesh, use_aggregate, use_speculate
    )
    if hub is not None:
        hub.start()
    try:
        for h in range(1, heights + 1):
            # Every validator runs the height concurrently; run_sequence
            # returns once the proposal is finalized on that node.
            await asyncio.gather(*(e.run_sequence(h) for e in engines))
    finally:
        if hub is not None:
            await hub.stop()
        for e in engines:
            e.messages.close()
            if e.speculator is not None:
                e.speculator.stop()

    _print_chains(engines)
    if use_speculate:
        stats = engines[0].speculator.stats()
        print(
            f"speculation: {stats['speculated_lanes']} lanes off-path, "
            f"{stats['cache_hits']} drain cache hits"
        )
    if hub is not None:
        stats = hub.stats()
        print(
            f"aggregation tree: {stats['certs_built']} certs, worst node "
            f"sent {max(stats['commit_bytes_per_node'])} commit bytes"
        )


async def main_chain(
    n: int,
    heights: int,
    use_device: bool,
    use_bls: bool = False,
    use_mesh: bool = False,
    use_aggregate: bool = False,
    use_speculate: bool = False,
    telemetry_port: Optional[int] = None,
) -> None:
    """The continuous-node mode: one ChainRunner per validator.

    Unlike :func:`main_async`'s per-height ``asyncio.gather`` barrier,
    each node owns ONE persistent runner task that drives heights
    back-to-back: finalized blocks and mid-round locks are WAL-persisted
    (``wal-<i>.jsonl`` in a temp dir — point it at real storage in a
    deployment and call ``runner.recover()`` on restart), a node that
    falls behind rejoins via batched block-sync, and buffered next-height
    traffic is pre-verified while the current height's COMMIT drain is in
    flight.  See docs/CHAIN.md.
    """
    import os
    import tempfile

    from go_ibft_tpu.chain import (
        ChainRunner,
        LoopbackSyncNetwork,
        SyncClient,
        WriteAheadLog,
    )
    from go_ibft_tpu.verify import HostBatchVerifier

    engines, certifier, hub = build_cluster(
        n, use_device, use_bls, use_mesh, use_aggregate, use_speculate
    )
    network = LoopbackSyncNetwork()
    runners = []
    with tempfile.TemporaryDirectory() as tmp:
        for i, engine in enumerate(engines):
            src = engine.backend.get_voting_powers
            runner = ChainRunner(
                engine,
                WriteAheadLog(os.path.join(tmp, f"wal-{i}.jsonl")),
                certifier=certifier,
                sync=SyncClient(
                    engine.backend.id(),
                    network,
                    engine.batch_verifier or HostBatchVerifier(src),
                    src,
                    cert_verifier=certifier,
                ),
            )
            network.register(engine.backend.id(), runner)
            runners.append(runner)
        telemetry = None
        if telemetry_port is not None:
            # The telemetry plane (docs/OBSERVABILITY.md): node 0 serves
            # /metrics (Prometheus text), /healthz (liveness; flips when
            # the runner wedges), and /statusz (height/round, breaker,
            # speculation + ring stats) while the chain runs.
            telemetry = runners[0].start_telemetry(port=telemetry_port)
            print(f"telemetry: {telemetry.url}/metrics /healthz /statusz")
        if hub is not None:
            hub.start()
        try:
            await asyncio.gather(
                *(r.run(until_height=heights) for r in runners)
            )
        finally:
            if telemetry is not None:
                telemetry.stop()
            if hub is not None:
                await hub.stop()
            for engine in engines:
                engine.messages.close()
        for i, runner in enumerate(runners):
            stats = runner.stats()
            print(
                f"validator {i}: height={runner.latest_height()} "
                f"handoff_ms_mean={stats['handoff_ms_mean']:.3f} "
                f"overlapped_lanes={stats['overlapped_lanes']} "
                f"synced={stats['synced_heights']}"
            )
    _print_chains(engines)


def main_serve(
    n: int, heights: int, clients: int, checkpoint_spacing: int = 0
) -> None:
    """Proof-serving mode (``--serve N``): run a chain to finality, then
    serve finality proofs to N synthetic light clients.

    The chain side is ``--chain`` in miniature (one ChainRunner per
    validator, no WAL — the serve layer reads the in-memory chain tail
    through the runner's ``SyncSource`` seam); the read side mounts a
    :class:`~go_ibft_tpu.serve.ProofServer` on runner 0 and hammers it
    from N client threads, each verifying its proof against the trusted
    genesis checkpoint.  Prints proofs/s and the cache hit rates — the
    docs/SERVING.md read-plane story at toy scale.

    ``--checkpoint-spacing S`` (ISSUE 20) additionally seals an epoch
    checkpoint certificate every S heights, serves the skip chain over a
    real HTTP :class:`~go_ibft_tpu.node.proof_api.ProofApiServer`, and
    cold-syncs a :class:`~go_ibft_tpu.lightsync.CheckpointClient`
    against it — printing checkpoint-anchored vs full-walk sync bytes.
    Proposals then carry next-set commitments so the tail proof verifies
    with ``require_commitments`` on (the fabricated-diff defense).
    """
    import threading
    import time

    from go_ibft_tpu.chain import ChainRunner
    from go_ibft_tpu.serve import ProofBuilder, ProofCache, ProofServer

    engines, _certifier, _hub = build_cluster(
        n, use_device=False, commit_next_set=checkpoint_spacing > 0
    )
    runners = [ChainRunner(engine, overlap=False) for engine in engines]

    async def drive() -> None:
        await asyncio.gather(*(r.run(until_height=heights) for r in runners))

    try:
        asyncio.run(drive())
    finally:
        for engine in engines:
            engine.messages.close()
    _print_chains(engines)

    source = runners[0]  # ChainRunner IS a SyncSource
    server = ProofServer(
        ProofBuilder(source, source.validators_for_height),
        ProofCache(chunk_heights=2),
    )
    verified = []
    t0 = time.perf_counter()

    def client(i: int) -> None:
        # staggered checkpoints: overlapping ranges share cached chunks
        checkpoint = i % max(1, heights - 1)
        proof = server.get_proof(checkpoint)
        # the trust anchor is the CHECKPOINT's next-height set — a client
        # must never verify against a set its checkpoint does not vouch
        # for (matters the moment the validator set rotates)
        server.verify_proof(
            proof, source.validators_for_height(checkpoint + 1)
        )
        verified.append(proof.target)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    stats = server.stats()
    print(
        f"served+verified {len(verified)} proofs in {elapsed * 1e3:.1f} ms "
        f"({len(verified) / elapsed:.1f} proofs/s)"
    )
    print(
        f"proof cache: {stats['cache']['hits']} hits / "
        f"{stats['cache']['misses']} misses "
        f"(hit rate {stats['cache']['hit_rate']}), "
        f"sig-verdict cache hit rate "
        f"{stats['verify']['sig_cache']['hit_rate']}"
    )

    if checkpoint_spacing <= 0:
        return

    # -- ISSUE 20: checkpoint-anchored cold sync over real HTTP ----------
    from go_ibft_tpu.crypto import bls as hbls
    from go_ibft_tpu.crypto.backend import proposal_hash_of
    from go_ibft_tpu.crypto.quorum_cert import BLSKeyRegistry
    from go_ibft_tpu.lightsync import CheckpointClient, Checkpointer
    from go_ibft_tpu.node.proof_api import ProofApiServer

    # Epoch certificates are BLS-sealed; register PoP-gated keys for the
    # same validator identities (rogue-key defense lives in the registry).
    addrs = sorted(source.validators_for_height(1))
    bls_signers = {
        a: hbls.BLSPrivateKey.from_seed(b"example-ckpt-bls-%d" % i)
        for i, a in enumerate(addrs)
    }
    registry = BLSKeyRegistry()
    for a, k in bls_signers.items():
        registry.register_key(a, k)
    checkpointer = Checkpointer(
        checkpoint_spacing, source.validators_for_height, signers=bls_signers
    )
    for block in source.get_blocks(1, source.latest_height()):
        checkpointer.on_finalize(
            block.height, proposal_hash_of(block.proposal)
        )

    api = ProofApiServer(
        server,
        source.latest_height,
        checkpoints_fn=checkpointer.wire_payload,
    )
    api.start()
    try:
        light = CheckpointClient(api.url, registry)
        genesis_powers = source.validators_for_height(1)
        report = light.cold_sync(genesis_powers)
        # Full-walk baseline over the SAME wire: one finality proof from
        # the genesis trust anchor, every height a diff hop.
        _, full_walk_bytes = light.fetch_proof(0, report.target)
        print(
            f"checkpoint sync (spacing {checkpoint_spacing}): anchored at "
            f"height {report.anchor_height} (epoch {report.anchor_epoch}), "
            f"skipped {report.heights_skipped} heights, "
            f"{report.pairing_dispatches} batched pairing dispatch(es)"
        )
        print(
            f"  checkpoint-anchored: {report.total_bytes} bytes "
            f"(certs {report.checkpoint_bytes} + bridges "
            f"{report.bridge_bytes} + tail {report.tail_bytes})"
        )
        print(
            f"  full walk from genesis: {full_walk_bytes} bytes "
            f"({full_walk_bytes / max(1, report.total_bytes):.1f}x)"
        )
    finally:
        api.stop()


def main_tenants(n: int, heights: int, tenants: int) -> None:
    """Multi-tenant mode (``--tenants N``): N independent chains — their
    own validator sets, proposals and WALs — share ONE process-wide
    :class:`~go_ibft_tpu.sched.TenantScheduler`, so every chain's verify
    drains coalesce into shared batched dispatches instead of issuing N
    small ones (docs/TENANCY.md).  Each chain runs in its own event-loop
    thread (the many-embedders-one-process posture); per-tenant drain
    latency SLOs print at the end from ``scheduler.stats()``.
    """
    import threading

    from go_ibft_tpu.chain import ChainRunner
    from go_ibft_tpu.core import BatchingIngress
    from go_ibft_tpu.sched import TenantScheduler

    scheduler = TenantScheduler(window_s=0.001, route="auto")

    async def one_chain(chain: int) -> list:
        keys = [
            PrivateKey.from_seed(b"tenant-%d-validator-%d" % (chain, i))
            for i in range(n)
        ]
        validators = ECDSABackend.static_validators({k.address: 1 for k in keys})
        nodes = []

        class _T:
            def multicast(self, message):
                for ingress in nodes:
                    ingress.submit(message)

        runners = []
        for i, key in enumerate(keys):
            handle = scheduler.register(
                f"chain-{chain}/node-{i}", validators, chain_id=f"chain-{chain}"
            )
            build = (
                lambda view, c=chain: b"tenant %d block %d" % (c, view.height)
            )  # noqa: E731
            engine = IBFT(
                StdoutLogger() if chain == 0 and i == 0 else _QuietLogger(),
                ECDSABackend(key, validators, build_proposal_fn=build),
                _T(),
                batch_verifier=handle,
            )
            engine.set_base_round_timeout(10.0)
            nodes.append(BatchingIngress(engine.add_messages))
            runners.append(ChainRunner(engine, overlap=False))
        try:
            await asyncio.gather(*(r.run(until_height=heights) for r in runners))
        finally:
            for r, ingress in zip(runners, nodes):
                ingress.close()
                r.engine.messages.close()
        return [b.proposal.raw_proposal for b in runners[0].chain]

    chains: dict = {}

    def chain_thread(chain: int) -> None:
        chains[chain] = asyncio.run(one_chain(chain))

    with scheduler:
        threads = [
            threading.Thread(target=chain_thread, args=(c,))
            for c in range(tenants)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    stats = scheduler.stats()
    for c in sorted(chains):
        blocks = [b.decode() for b in chains[c]]
        p99 = max(
            (
                t["drain_p99_ms"]
                for t in stats["tenants"].values()
                if t["chain"] == f"chain-{c}" and t["drain_p99_ms"] is not None
            ),
            default=None,
        )
        print(f"chain {c}: {blocks} drain_p99_ms={p99}")
    print(
        f"scheduler: {stats['coalesced_requests']} requests coalesced into "
        f"{stats['dispatches']} dispatches "
        f"(ratio {stats['coalesce_ratio']}), "
        f"{stats['flush_faults']} flush faults"
    )


class _QuietLogger:
    def info(self, msg, *args):
        pass

    debug = error = info


def _print_chains(engines) -> None:
    from go_ibft_tpu.lightsync import strip_next_set

    for i, e in enumerate(engines):
        # Commitment-carrying proposals end in a 52-byte binary suffix
        # (lightsync.commitment) — strip it for the human-readable chain.
        chain = [
            strip_next_set(p.raw_proposal).decode()
            for p, _seals in e.backend.inserted
        ]
        _p, last_seals = e.backend.inserted[-1]
        if e.finalized_certificate is not None:
            evidence = (
                f"one {len(e.finalized_certificate.encode())}-byte "
                "aggregate certificate"
            )
        else:
            evidence = f"{len(last_seals)} seals"
        print(f"validator {i}: chain={chain} (last block carries {evidence})")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--heights", type=int, default=3)
    ap.add_argument(
        "--device",
        action="store_true",
        help="verify PREPARE/COMMIT phases through the device kernels",
    )
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="shard large verify drains lane-parallel across the device "
        "mesh (AdaptiveBatchVerifier + MeshBatchVerifier; degrades to "
        "--device behavior on a 1-device host)",
    )
    ap.add_argument(
        "--bls",
        action="store_true",
        help="BLS12-381 committed seals (one pairing certifies a quorum)",
    )
    ap.add_argument(
        "--aggregate",
        action="store_true",
        help="the full aggregate-COMMIT mode (implies --bls): PoP-gated "
        "key registry, aggregation-tree COMMIT dissemination, engines "
        "finalize from one O(1) quorum certificate, and (--chain) the "
        "WAL/sync carry certificates instead of per-validator seals",
    )
    ap.add_argument(
        "--speculate",
        action="store_true",
        help="speculative cross-phase verification: COMMIT seals verify "
        "off the event loop as they arrive (before their phase opens) "
        "and the commit drain early-exits at quorum (ISSUE 9)",
    )
    ap.add_argument(
        "--chain",
        action="store_true",
        help="drive heights through ChainRunners (persistent per-node "
        "height loops, WAL + block-sync) instead of the per-height "
        "gather barrier",
    )
    ap.add_argument(
        "--tenants",
        type=int,
        default=0,
        metavar="N",
        help="multi-tenant mode: run N independent chains (each --nodes "
        "validators) whose verify drains coalesce through ONE process-"
        "wide TenantScheduler (docs/TENANCY.md); prints per-tenant drain "
        "p99 and the coalesce ratio",
    )
    ap.add_argument(
        "--telemetry",
        type=int,
        default=None,
        metavar="PORT",
        help="(--chain mode) mount the telemetry endpoints on node 0: "
        "/metrics (Prometheus text), /healthz (liveness), /statusz "
        "(operator status JSON); 0 binds an ephemeral port "
        "(docs/OBSERVABILITY.md)",
    )
    ap.add_argument(
        "--serve",
        type=int,
        default=0,
        metavar="N",
        help="proof-serving mode: finalize --heights blocks, then serve "
        "finality proofs to N synthetic light clients through a "
        "ProofServer mounted on the chain (docs/SERVING.md); prints "
        "proofs/s and cache hit rates",
    )
    ap.add_argument(
        "--checkpoint-spacing",
        type=int,
        default=0,
        metavar="S",
        help="(--serve mode) seal an epoch checkpoint certificate every S "
        "heights and cold-sync a CheckpointClient over real HTTP; prints "
        "checkpoint-anchored vs full-walk sync bytes (docs/SERVING.md)",
    )
    args = ap.parse_args()
    if args.serve:
        main_serve(
            args.nodes, args.heights, args.serve, args.checkpoint_spacing
        )
    elif args.tenants:
        main_tenants(args.nodes, args.heights, args.tenants)
    else:
        if args.chain:
            asyncio.run(
                main_chain(
                    args.nodes,
                    args.heights,
                    args.device,
                    args.bls,
                    args.mesh,
                    args.aggregate,
                    args.speculate,
                    telemetry_port=args.telemetry,
                )
            )
        else:
            asyncio.run(
                main_async(
                    args.nodes,
                    args.heights,
                    args.device,
                    args.bls,
                    args.mesh,
                    args.aggregate,
                    args.speculate,
                )
            )
