#!/usr/bin/env python3
"""chip_smoke.py — the served ECDSA path, once, on the chip; or a failure.

The deployment is ``BASELINE.json``'s headline: one validator of a
100-validator ECDSA committee (equal power, quorum 67) receiving real signed
PREPREPARE / PREPARE / COMMIT traffic through the normal seam — ingress ->
``IBFT.add_messages`` -> ``verify_senders`` / seal drains -> the verifier ->
the recover kernel on the TPU — and finalizing heights.  The system keeps
kilobytes on the device; what is real here is the committee.

The parent NEVER initializes a JAX backend (a chip belongs to one process):
it runs the phases as successive child processes, each owning the chip alone,
all sharing one compile cache (``JAX_COMPILATION_CACHE_DIR`` where set, else
``<checkout>/.cache/xla`` — ``go_ibft_tpu/utils/jaxcache.py``):

* ``kernels`` — differential gate before anything else: the 8-lane and
  committee-width ``digest_words`` / ``ecdsa_recover`` programs against the
  sequential host oracle, lane for lane, corrupted lanes included; and the Pallas keccak COMPILED against its uint64 oracle.
* ``engine`` — one ``IBFT`` with a default-constructed
  ``AdaptiveBatchVerifier`` and ``BatchingIngress`` finalizes heights in
  round 0 with corrupted peers every height; the cost ledger and the
  metrics — not a flag — must show the drains served by the device route
  with no fallback, no breaker demotion, no quarantined lane and no compile
  after warm-up.
* ``node`` — the binary users deploy (``python -m go_ibft_tpu.node``,
  ``[sched] route = "device"``); this process plays the other validators
  over the real gRPC transport, then verifies ``GET /proof`` on the host,
  reads what served the flushes from ``/statusz``, and drains the node with
  SIGTERM.

Every phase prints one JSON line naming the platform, device kind and count
it ran on, what it checked, and its set-up (compile or cache-load) seconds.
Any failing phase makes the run exit non-zero.  There is no CPU mode: without
a TPU the first child fails in seconds, naming the platform it found.  The
last stdout line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Data comes from ``--seed`` through the native signer (``go_ibft_tpu/native``
builds ``libibft_native.so`` from its source on first use); every line says
``native: true|false`` so a missing compiler shows as that, not as a silent
pure-Python run.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
PHASES = ("kernels", "engine", "node")
NO_ACCELERATOR_RC = 3


class SmokeFailure(AssertionError):
    """A check the smoke makes did not hold."""


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# the committee and its signed traffic (host only: initializes no backend)
# ---------------------------------------------------------------------------


class Committee:
    """``n`` seeded validators; one of them is the node under test.

    The node's key is picked so that it proposes in none of the smoke's
    heights (proposer = sorted addresses ``[(height + round) % n]``): the
    other validators are pre-signed peers, and a proposal has to come from
    one of them.
    """

    def __init__(self, n: int, seed: int, heights: int) -> None:
        from go_ibft_tpu import native
        from go_ibft_tpu.crypto import PrivateKey
        from go_ibft_tpu.crypto.backend import ECDSABackend

        self.native = native.install()
        self.seed = seed
        self.seeds = [f"chip-smoke-{seed}-{i}" for i in range(n)]
        self.keys = [PrivateKey.from_seed(s.encode()) for s in self.seeds]
        self.powers = {k.address: 1 for k in self.keys}
        self.src = ECDSABackend.static_validators(self.powers)
        self.backends = {k.address: ECDSABackend(k, self.src) for k in self.keys}
        self.key_of = {k.address: k for k in self.keys}
        ordered = sorted(self.powers)
        self.proposer = {h: ordered[h % n] for h in range(1, heights + 1)}
        proposers = set(self.proposer.values())
        node_index = next(
            i for i, k in enumerate(self.keys) if k.address not in proposers
        )
        self.node_seed = self.seeds[node_index]
        self.node = self.keys[node_index].address
        self.quorum = (2 * n) // 3 + 1

    def traffic(self, height: int, corrupt: int) -> dict:
        """One height of peer traffic, ``corrupt`` peers' signatures bad.

        Every corrupted peer's PREPARE envelope signature is mangled.  Half
        of them also mangle their COMMIT envelope; the other half send a
        COMMIT whose envelope is VALID around a mangled committed seal, so
        both the ingress batch and the seal drain have lanes to reject.
        """
        import numpy as np

        from go_ibft_tpu.crypto import ecdsa as ec
        from go_ibft_tpu.crypto.backend import encode_signature
        from go_ibft_tpu.crypto.keccak import keccak256
        from go_ibft_tpu.messages.wire import (
            CommitMessage,
            IbftMessage,
            MessageType,
            View,
        )

        view = View(height=height, round=0)
        proposer = self.proposer[height]
        preprepare = self.backends[proposer].build_preprepare_message(
            b"chip smoke block %d" % height, None, view
        )
        phash = preprepare.preprepare_data.proposal_hash
        peers = [a for a in self.powers if a != self.node]
        followers = [a for a in peers if a != proposer]
        rng = np.random.default_rng([self.seed, height])
        bad = [
            followers[i]
            for i in rng.choice(len(followers), size=corrupt, replace=False)
        ]
        bad_envelope = set(bad[: corrupt // 2])
        bad_seal = set(bad[corrupt // 2 :])

        def mangle(sig: bytes) -> bytes:
            out = bytearray(sig)
            out[5] ^= 0xFF  # inside r: recovers to another key, or to none
            return bytes(out)

        prepares = []
        for addr in followers:  # the proposer's PREPARE would void the quorum
            msg = self.backends[addr].build_prepare_message(phash, view)
            if addr in bad_envelope or addr in bad_seal:
                msg.signature = mangle(msg.signature)
            prepares.append(msg)
        commits = []
        for addr in peers:
            if addr in bad_seal:
                seal = mangle(
                    encode_signature(*ec.sign(self.key_of[addr], phash))
                )
                msg = IbftMessage(
                    view=view.copy(),
                    sender=addr,
                    type=MessageType.COMMIT,
                    commit_data=CommitMessage(
                        proposal_hash=phash, committed_seal=seal
                    ),
                )
                digest = keccak256(msg.encode(include_signature=False))
                msg.signature = encode_signature(
                    *ec.sign(self.key_of[addr], digest)
                )
            else:
                msg = self.backends[addr].build_commit_message(phash, view)
                if addr in bad_envelope:
                    msg.signature = mangle(msg.signature)
            commits.append(msg)
        return {
            "height": height,
            "preprepare": preprepare,
            "prepares": prepares,
            "commits": commits,
            "phash": phash,
            "bad": bad_envelope | bad_seal,
        }

    def check_finalized(self, height: int, round_: int, seals, traffic) -> None:
        """A finalized height, held to the guarantees: round 0, every seal
        valid by the sequential host oracle, quorum power, nothing from a
        corrupted peer."""
        from go_ibft_tpu.verify import HostBatchVerifier

        _check(round_ == 0, f"height {height} finalized in round {round_}, not 0")
        mask = HostBatchVerifier(self.src).verify_committed_seals(
            traffic["phash"], list(seals), height
        )
        _check(bool(mask.all()), f"height {height}: a finalized seal is not oracle-valid")
        signers = {s.signer for s in seals}
        _check(
            sum(self.powers.get(a, 0) for a in signers) >= self.quorum,
            f"height {height}: finalized seals carry less than quorum power",
        )
        _check(
            not signers & traffic["bad"],
            f"height {height}: a corrupted peer's seal was finalized",
        )


# ---------------------------------------------------------------------------
# child-side plumbing: device, cache and compile evidence
# ---------------------------------------------------------------------------


def _device() -> dict:
    """The device as JAX reports it (initializes the backend: child only)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


class _CompileEvents:
    """Counts XLA compile requests and how the persistent cache answered,
    from JAX's own monitoring events (every program, not only the ones a
    dispatch seam watches)."""

    def __init__(self) -> None:
        import jax

        self.requests = 0
        self.hits = 0
        self.misses = 0

        def on_event(name: str, **_kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(name: str, _secs: float, **_kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return {
            "compile_requests": self.requests,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def kernels_phase(
    validators: int = 100, seed: int = 0, *, pallas_interpret: bool = False
) -> dict:
    """The programs the served path dispatches, against the host oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from go_ibft_tpu import native
    from go_ibft_tpu.bench.workload import build_signed_round
    from go_ibft_tpu.crypto.backend import ECDSABackend
    from go_ibft_tpu.crypto.keccak import keccak256
    from go_ibft_tpu.ops.pallas_keccak import keccak_f_pallas, keccak_f_reference
    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache
    from go_ibft_tpu.verify import HostBatchVerifier
    from go_ibft_tpu.verify import batch as vbatch

    have_native = native.install()
    events = _CompileEvents()
    cache_dir = enable_persistent_cache()
    setup_s: dict = {}
    checked: dict = {}

    def first_call(name: str, fn, *args):
        """Run ``fn`` once to completion; its wall is the program's set-up
        (trace + compile, or trace + cache load) plus one execution."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        setup_s[name] = round(time.perf_counter() - t0, 3)
        return out

    for n in (8, validators):
        sr = build_signed_round(n, corrupt_frac=0.25, seed=seed)
        w = sr.pack()
        blocks, counts, r, s, v, senders, live = w.prepare
        lanes, rows = blocks.shape[0], w.table.shape[0]
        tag = f"{lanes}l"
        oracle = HostBatchVerifier(
            ECDSABackend.static_validators({m.sender: 1 for m in sr.prepares})
        )
        want_prepare = np.zeros(lanes, dtype=bool)
        want_prepare[:n] = oracle.verify_senders(sr.prepares)
        want_seals = np.zeros(lanes, dtype=bool)
        want_seals[:n] = oracle.verify_committed_seals(
            sr.proposal_hash, sr.seals, sr.height
        )
        _check(
            (want_prepare[:n] == sr.expected_prepare_mask).all()
            and (want_seals[:n] == sr.expected_seal_mask).all(),
            f"host oracle disagrees with the workload's own labels at n={n}",
        )
        n_bad = int(n - want_prepare.sum())
        _check(n_bad > 0, f"no corrupted lane in the n={n} workload")

        zw = np.asarray(
            first_call(
                f"digest_words_{tag}",
                vbatch._digest_kernel,
                jnp.asarray(blocks),
                jnp.asarray(counts),
            )
        )
        want_zw = np.stack(
            [
                np.frombuffer(
                    keccak256(m.encode(include_signature=False)), ">u4"
                )[::-1]
                for m in sr.prepares
            ]
        )
        _check(
            (zw[:n] == want_zw).all(),
            f"digest_words_{tag} disagrees with the host keccak",
        )

        table = jnp.asarray(w.table)
        args = tuple(jnp.asarray(a) for a in (zw, r, s, v, senders)) + (
            table,
            jnp.asarray(live),
        )
        mask = np.asarray(
            first_call(f"ecdsa_recover_{tag}", vbatch._recover_kernel, *args)
        )
        _check(
            (mask == want_prepare).all(),
            f"ecdsa_recover_{tag} (envelopes): lanes "
            f"{np.nonzero(mask != want_prepare)[0].tolist()} disagree with the oracle",
        )
        hz, r2, s2, v2, signers, live2 = w.seals
        smask = np.asarray(
            vbatch._recover_kernel(
                *(jnp.asarray(a) for a in (hz, r2, s2, v2, signers)),
                table,
                jnp.asarray(live2),
            )
        )
        _check(
            (smask == want_seals).all(),
            f"ecdsa_recover_{tag} (seals): lanes "
            f"{np.nonzero(smask != want_seals)[0].tolist()} disagree with the oracle",
        )

        checked[tag] = {
            "validators": n,
            "lanes": lanes,
            "table_rows": rows,
            "corrupted_lanes_rejected": n_bad,
        }

    # The Pallas keccak, compiled by Mosaic (the interpreter is for the CPU
    # tests only): one 128-message tile, and a grid of three.
    rng = np.random.default_rng(seed)
    for tiles in (1, 3):
        state = rng.integers(0, 1 << 32, size=(128 * tiles, 25, 2), dtype=np.uint32)
        got = np.asarray(
            first_call(
                f"pallas_keccak_{tiles}x128",
                lambda st: keccak_f_pallas(st, interpret=pallas_interpret),
                jnp.asarray(state),
            )
        )
        _check(
            (got == keccak_f_reference(state)).all(),
            f"pallas keccak ({tiles} tile(s)) disagrees with keccak_f_reference",
        )
    checked["pallas_keccak"] = {
        "tiles": [1, 3],
        "compiled": not pallas_interpret,
    }
    return {
        "native": have_native,
        "cache_dir": cache_dir,
        "setup_s": setup_s,
        "checked": checked,
        **events.snapshot(),
    }


# ---------------------------------------------------------------------------
# phase: engine
# ---------------------------------------------------------------------------


async def _await_message(sent: list, type_, height: int, timeout_s: float):
    """Wait until the validator under test has multicast a ``type_`` for
    ``height`` (``sent`` grows as its transport hands messages over)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for m in sent:
            if m.type == type_ and m.view.height == height:
                return m
        await asyncio.sleep(0.002)
    raise SmokeFailure(
        f"height {height}: no {type_.name} from the validator under test "
        f"in {timeout_s:.0f}s"
    )


class _NullLogger:
    def info(self, *a) -> None:
        pass

    debug = info

    def error(self, msg, *args) -> None:
        print(f"[engine error] {msg} {args}", file=sys.stderr, flush=True)


def engine_phase(
    validators: int = 100, heights: int = 5, corrupt: int = 10, seed: int = 0
) -> dict:
    """One engine of the committee, its default verifier and ingress."""
    import numpy as np

    from go_ibft_tpu.core import IBFT, BatchingIngress
    from go_ibft_tpu.messages.wire import MessageType
    from go_ibft_tpu.obs import ledger as cost_ledger
    from go_ibft_tpu.utils import calibration, metrics
    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache
    from go_ibft_tpu.verify import AdaptiveBatchVerifier, HostBatchVerifier
    from go_ibft_tpu.verify import batch as vbatch
    from go_ibft_tpu.verify.pipeline import BREAKER_TRANSITIONS_KEY

    committee = Committee(validators, seed, heights)
    events = _CompileEvents()
    cache_dir = enable_persistent_cache()
    ledger_owned = not cost_ledger.enabled()
    if ledger_owned:
        cost_ledger.enable()
    try:
        measured = calibration.measured_cutover()
        verifier = AdaptiveBatchVerifier(committee.src)
        lane_bucket = _lane_bucket(validators)
        table_rows = vbatch._bucket(validators, vbatch._TABLE_BUCKETS)
        _check(
            verifier.cutover <= validators * 2 // 3,
            f"cutover {verifier.cutover} would host-route this committee's drains",
        )

        # Warm what this committee makes the verifier dispatch: a phase's
        # flood (at twice the committee's lane bucket where that is free,
        # each COMMIT's seal beside its envelope), a seal drain that missed
        # the verdicts at the bucket itself, and the 8-lane bucket an
        # early-exit seal drain takes when only a few lanes are still owed.
        t0 = time.perf_counter()
        verifier.warmup(
            lanes=(8, *vbatch.committee_lanes(validators)),
            blocks=(2,),
            table_rows=table_rows,
        )
        warmup_s = round(time.perf_counter() - t0, 3)
        warm = cost_ledger.snapshot()
        warm_events = events.snapshot()
        ladder_keys = {
            "drain_faults": vbatch.DRAIN_FAULTS_KEY,
            "quarantined_lanes": vbatch.QUARANTINED_LANES_KEY,
            "breaker_transitions": BREAKER_TRANSITIONS_KEY,
        }
        counters_before = {
            name: metrics.get_counter(key) for name, key in ladder_keys.items()
        }

        class _Wire:
            """The engine's transport: keeps what it sent, and delivers it
            back through the same ingress as everyone else's messages."""

            def __init__(self) -> None:
                self.sent: list = []
                self.ingress = None

            def multicast(self, message) -> None:
                self.sent.append(message)
                self.ingress.submit(message)

        wire = _Wire()
        backend = committee.backends[committee.node]
        engine = IBFT(_NullLogger(), backend, wire, batch_verifier=verifier)
        batches: list = []

        def add_messages(batch) -> None:
            batches.append(len(batch))
            engine.add_messages(batch)

        ingress = BatchingIngress(add_messages)
        wire.ingress = ingress
        locked: dict = {}
        engine.on_lock = lambda h, r, cert, proposal: locked.setdefault(
            h, list(cert.prepare_messages)
        )
        oracle = HostBatchVerifier(committee.src)
        all_traffic = {
            h: committee.traffic(h, corrupt) for h in range(1, heights + 1)
        }
        round_timeout_s = engine.base_round_timeout

        async def drive() -> None:
            for h in range(1, heights + 1):
                t = all_traffic[h]
                sequence = asyncio.create_task(engine.run_sequence(h))
                await asyncio.sleep(0)
                ingress.submit(t["preprepare"])
                await _await_message(wire.sent, MessageType.PREPARE, h, round_timeout_s)
                for m in t["prepares"]:
                    ingress.submit(m)
                await _await_message(wire.sent, MessageType.COMMIT, h, round_timeout_s)
                for m in t["commits"]:
                    ingress.submit(m)
                await asyncio.wait_for(sequence, 3 * round_timeout_s)

        t0 = time.perf_counter()
        try:
            asyncio.run(drive())
        finally:
            engine.messages.close()
        run_s = round(time.perf_counter() - t0, 3)
        # Taken before the oracle checks below: the oracle is a host
        # verifier, and its own drains land in the same ledger.
        ran = cost_ledger.snapshot()

        _check(
            len(backend.inserted) == heights,
            f"finalized {len(backend.inserted)} of {heights} heights",
        )
        for h, (proposal, seals) in enumerate(backend.inserted, start=1):
            t = all_traffic[h]
            committee.check_finalized(h, proposal.round, seals, t)
            prepares = locked.get(h)
            _check(prepares is not None, f"height {h}: no prepare quorum was locked")
            _check(
                bool(oracle.verify_senders(prepares).all())
                and not {m.sender for m in prepares} & t["bad"],
                f"height {h}: a corrupted PREPARE was accepted",
            )

        # What served the drains — from the ledger and the metrics.
        def rows(snap: dict) -> dict:
            return {
                (r["program"], r["route"]): r for r in snap["dispatches"]
            }

        before, after = rows(warm), rows(ran)

        def delta(key, field: str) -> int:
            return after.get(key, {}).get(field, 0) - before.get(key, {}).get(
                field, 0
            )

        device_key = ("ecdsa_recover", "device")
        host_key = ("ecdsa_recover", "host")
        device_dispatches = delta(device_key, "dispatches")
        device_padded = delta(device_key, "padded_lanes")
        device_live = delta(device_key, "live_lanes")
        host_live = delta(host_key, "live_lanes")
        small = sum(b for b in batches if b < verifier.cutover)
        big = sum(b for b in batches if b >= verifier.cutover)
        _check(big > 0, "ingress never delivered a cutover-sized batch")
        flood_lanes = vbatch.committee_lanes(validators)[-1]
        _check(
            device_dispatches > 0
            and device_padded == device_dispatches * flood_lanes,
            f"device route: {device_dispatches} dispatches over {device_padded} "
            f"padded lanes, expected every one at {flood_lanes} lanes",
        )
        # Every lane of a >= cutover batch (and every seal lane of a quorum
        # drain) was the device's; the host rows hold exactly the lanes of
        # the sub-cutover batches and nothing else.
        _check(
            host_live == small,
            f"host route served {host_live} lanes; the sub-cutover ingress "
            f"batches carried {small} — a device-sized drain was host-served",
        )
        _check(
            device_live >= big,
            f"device route served {device_live} live lanes, ingress alone "
            f"delivered {big} in cutover-sized batches",
        )
        other_routes = sorted(
            {
                route
                for (program, route) in after
                if delta((program, route), "dispatches") > 0
                and route not in ("device", "host")
            }
        )
        _check(not other_routes, f"dispatches on unexpected routes {other_routes}")
        counters = {
            name: metrics.get_counter(key) - counters_before[name]
            for name, key in ladder_keys.items()
        }
        for name, moved in counters.items():
            _check(moved == 0, f"{name} moved by {moved}: the ladder fired")
        _check(verifier.breaker.level == 0, "the breaker is demoted")
        end_events = events.snapshot()
        compiles_after_warmup = sum(
            rec["count"] for rec in ran["compiles"].values()
        ) - sum(rec["count"] for rec in warm["compiles"].values())
        xla_after_warmup = (
            end_events["compile_requests"] - warm_events["compile_requests"]
        )
        _check(
            compiles_after_warmup == 0 and xla_after_warmup == 0,
            f"compile events after warm-up: ledger {compiles_after_warmup}, "
            f"xla {xla_after_warmup}",
        )
        return {
            "native": committee.native,
            "cache_dir": cache_dir,
            "validators": validators,
            "quorum": committee.quorum,
            "heights_finalized_round0": heights,
            "corrupted_peers_per_height": corrupt,
            "cutover": verifier.cutover,
            "cutover_source": (
                "default" if measured is None else "calibration.json"
            ),
            "warmup_s": warmup_s,
            "setup_s": {
                program: round(rec["ms"] / 1e3, 3)
                for program, rec in warm["compiles"].items()
            },
            "run_s": run_s,
            "ingress_batches": batches,
            "ledger": {
                "device_dispatches": device_dispatches,
                "device_padded_lanes": device_padded,
                "device_live_lanes": device_live,
                "host_live_lanes": host_live,
            },
            "counters": counters,
            "compiles_after_warmup": compiles_after_warmup,
            **end_events,
        }
    finally:
        if ledger_owned:
            cost_ledger.disable()


# ---------------------------------------------------------------------------
# phase: node (driven from the parent; the node is the child)
# ---------------------------------------------------------------------------


async def _get(port: int, path: str, timeout_s: float = 10.0):
    from go_ibft_tpu.sim.fleet import _http_get

    return await asyncio.get_running_loop().run_in_executor(
        None, _http_get, "127.0.0.1", port, path, timeout_s
    )


def _stop_process_group(proc) -> None:
    if proc.returncode is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


async def node_phase(
    validators: int = 100,
    heights: int = 5,
    corrupt: int = 10,
    seed: int = 0,
    *,
    boot_timeout_s: float = 900.0,
    require_platform: str | None = "tpu",
) -> dict:
    """The deployed binary on the device route; this process is its peers."""
    from go_ibft_tpu.messages.wire import MessageType
    from go_ibft_tpu.net import GrpcTransport
    from go_ibft_tpu.node.config import (
        ConsensusConfig,
        NodeConfig,
        ProofApiConfig,
        TelemetryConfig,
        TraceConfig,
    )
    from go_ibft_tpu.serve import FinalityProof, ProofVerifier

    committee = Committee(validators, seed, heights)
    all_traffic = {h: committee.traffic(h, corrupt) for h in range(1, heights + 1)}
    from_node: list = []  # what the node multicast to its peers (all of them: us)

    def deliver(message) -> None:
        if message.sender == committee.node:
            from_node.append(message)

    transport = GrpcTransport("127.0.0.1:0", {}, deliver)
    await transport.start()
    proc = None
    with tempfile.TemporaryDirectory(prefix="chip_smoke_node_") as run_dir:
        config = NodeConfig(
            node_id=0,
            key_seed=committee.node_seed,
            data_dir=os.path.join(run_dir, "data"),
            validators={a.hex(): p for a, p in committee.powers.items()},
            heights=0,
            consensus=ConsensusConfig(
                listen="127.0.0.1:0",
                peers={"committee": f"127.0.0.1:{transport.bound_port}"},
            ),
            proof_api=ProofApiConfig(listen="127.0.0.1:0"),
            telemetry=TelemetryConfig(listen="127.0.0.1:0"),
            trace=TraceConfig(enabled=False),
            sched_enabled=True,
            sched_route="device",
        )
        config_path = os.path.join(run_dir, "node.toml")
        with open(config_path, "w", encoding="utf-8") as fh:
            fh.write(config.to_toml())
        err_path = os.path.join(run_dir, "node.err.log")
        try:
            t0 = time.monotonic()
            with open(err_path, "wb") as err_fh:
                proc = await asyncio.create_subprocess_exec(
                    sys.executable,
                    "-m",
                    "go_ibft_tpu.node",
                    "--config",
                    config_path,
                    stdout=asyncio.subprocess.PIPE,
                    stderr=err_fh,
                    cwd=REPO,
                    start_new_session=True,
                )
            try:
                raw = await asyncio.wait_for(
                    proc.stdout.readline(), boot_timeout_s
                )
            except asyncio.TimeoutError:
                raise SmokeFailure(
                    f"no boot line from the node in {boot_timeout_s:.0f}s"
                ) from None
            _check(raw, "the node exited before its boot line")
            boot = json.loads(raw)
            _check("node_boot" in boot, f"unexpected first line from the node: {boot}")
            boot_s = round(time.monotonic() - t0, 3)
            device = boot.get("device")
            _check(device is not None, "the boot line names no device")
            if require_platform is not None and device["platform"] != require_platform:
                raise SmokeFailure(
                    f"the node runs on {device['platform']!r}, not {require_platform!r}"
                )
            transport.add_peer("node", f"127.0.0.1:{boot['consensus_port']}")
            code, booted = await _get(boot["telemetry_port"], "/statusz")
            _check(code == 200, f"/statusz answered {code} after boot")
            boot_ledger = booted["cost_ledger"]
            _check(boot_ledger is not None, "the node's cost ledger is off")

            round_timeout_s = config.consensus.base_round_timeout_s
            t_run = time.monotonic()
            for h in range(1, heights + 1):
                t = all_traffic[h]
                transport.multicast(t["preprepare"])
                await _await_message(from_node, MessageType.PREPARE, h, round_timeout_s)
                for m in t["prepares"]:
                    transport.multicast(m)
                await _await_message(from_node, MessageType.COMMIT, h, round_timeout_s)
                for m in t["commits"]:
                    transport.multicast(m)
                deadline = time.monotonic() + round_timeout_s
                while True:
                    code, head = await _get(boot["proof_api_port"], "/head")
                    if code == 200 and head["head"] >= h:
                        break
                    _check(
                        time.monotonic() < deadline,
                        f"height {h} not finalized within the round-0 timeout",
                    )
                    await asyncio.sleep(0.02)
            run_s = round(time.monotonic() - t_run, 3)
            round_changes = [
                m
                for m in from_node
                if m.type == MessageType.ROUND_CHANGE and m.view.height <= heights
            ]
            _check(not round_changes, "the node changed round inside the smoke's heights")

            code, payload = await _get(
                boot["proof_api_port"],
                f"/proof?checkpoint=0&target={heights}",
                timeout_s=60.0,
            )
            _check(code == 200, f"GET /proof answered {code}: {payload}")
            proof = FinalityProof.from_wire(payload["proof"])
            verifier = ProofVerifier()  # the sequential host oracle
            try:
                report = verifier.verify(proof, committee.powers)
            finally:
                verifier.close()
            _check(
                [e.height for e in proof.entries] == list(range(1, heights + 1)),
                "the proof does not cover the smoke's heights",
            )
            for entry in proof.entries:
                committee.check_finalized(
                    entry.height,
                    entry.proposal.round,
                    entry.seals,
                    all_traffic[entry.height],
                )

            code, status = await _get(boot["telemetry_port"], "/statusz")
            _check(code == 200, f"/statusz answered {code}")
            sched = status["sched"]
            served = sched["served"]
            lane_bucket = _lane_bucket(validators)
            _check(
                served.get(f"device/{lane_bucket}", 0) > 0,
                f"no device-route dispatch at {lane_bucket} lanes: {served}",
            )
            _check(
                all(key.startswith("device/") for key in served),
                f"a flush was not served by the device route: {served}",
            )
            _check(sched["flush_faults"] == 0, f"flush faults: {sched['flush_faults']}")
            shed = {
                tid: row["shed_lanes"]
                for tid, row in sched["tenants"].items()
                if row["shed_lanes"]
            }
            _check(not shed, f"lanes shed to the host oracle: {shed}")
            ledger = status["cost_ledger"]
            _check(
                ledger["compiles"] == boot_ledger["compiles"],
                f"compile events after boot: {boot_ledger['compiles']} -> "
                f"{ledger['compiles']}",
            )

            proc.send_signal(signal.SIGTERM)
            try:
                rc = await asyncio.wait_for(proc.wait(), 10.0)
            except asyncio.TimeoutError:
                raise SmokeFailure("the node did not exit within 10s of SIGTERM") from None
            _check(rc == 0, f"the node exited {rc} after SIGTERM")
            tail = (await proc.stdout.read()).decode("utf-8").strip().splitlines()
            _check(tail, "no drain report on the node's stdout")
            drain = json.loads(tail[-1])
            _check(
                drain.get("chain_height", 0) >= heights,
                f"drain report at chain height {drain.get('chain_height')}",
            )
            with open(drain["wal_path"], "rb") as fh:
                wal = [json.loads(line) for line in fh.read().splitlines() if line]
            _check(len(wal) >= heights, f"the WAL holds {len(wal)} records")
        except BaseException:
            try:
                with open(err_path, "r", encoding="utf-8", errors="replace") as fh:
                    sys.stderr.write(fh.read()[-4000:])
            except OSError:
                pass
            raise
        finally:
            if proc is not None:
                _stop_process_group(proc)
                await proc.wait()
            await transport.stop()
    return {
        "native": committee.native,
        "platform": device["platform"],
        "kind": device["kind"],
        "count": device["count"],
        "cache_dir": boot.get("compile_cache"),
        "validators": validators,
        "heights_finalized_round0": heights,
        "corrupted_peers_per_height": corrupt,
        "boot_s": boot_s,
        "setup_s": {"boot_compiles_total": round(boot_ledger["compile_ms"] / 1e3, 3)},
        "boot_compile_events": boot_ledger["compiles"],
        "compiles_after_boot": ledger["compiles"] - boot_ledger["compiles"],
        "run_s": run_s,
        "served": served,
        "sched_dispatches": sched["dispatches"],
        "proof": {"lanes": report["lanes"], "heights": report["heights"]},
        "drain": {"rc": rc, "chain_height": drain["chain_height"]},
        "wal_records": len(wal),
    }


def _lane_bucket(validators: int) -> int:
    from go_ibft_tpu.verify import batch as vbatch

    return vbatch._bucket(validators, vbatch._BATCH_BUCKETS)


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------


def _child_main(args) -> int:
    """One in-process phase, in a process that owns the chip alone."""
    device = _device()
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: no accelerator — JAX found platform "
            f"{device['platform']!r} ({device['count']} x {device['kind']}); "
            "this script has no CPU mode",
            file=sys.stderr,
            flush=True,
        )
        return NO_ACCELERATOR_RC
    if args.phase == "kernels":
        result = kernels_phase(args.validators, args.seed)
    else:
        result = engine_phase(args.validators, args.heights, args.corrupt, args.seed)
    print(json.dumps({"phase": args.phase, "ok": True, **device, **result}), flush=True)
    return 0


def _run_child(phase: str, args) -> dict:
    """Run one phase in a child; returns its line or raises SmokeFailure."""
    cmd = [
        sys.executable,
        os.path.abspath(__file__),
        "--phase",
        phase,
        "--validators",
        str(args.validators),
        "--heights",
        str(args.heights),
        "--corrupt",
        str(args.corrupt),
        "--seed",
        str(args.seed),
    ]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, cwd=REPO, start_new_session=True
    )
    try:
        out, _ = proc.communicate(timeout=args.phase_timeout)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            f"phase {phase} exceeded {args.phase_timeout:.0f}s"
        ) from None
    finally:
        _stop_process_group(proc)
    if proc.returncode != 0:
        failure = SmokeFailure(f"phase {phase} exited {proc.returncode}")
        failure.rc = proc.returncode
        raise failure
    lines = out.decode("utf-8").strip().splitlines()
    _check(lines, f"phase {phase} printed no line")
    return json.loads(lines[-1])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--validators", type=int, default=100)
    parser.add_argument("--heights", type=int, default=5)
    parser.add_argument(
        "--corrupt", type=int, default=10, help="corrupted peers per height"
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--phases", default=",".join(PHASES), help="comma-separated subset, in order"
    )
    parser.add_argument(
        "--phase-timeout",
        type=float,
        default=900.0,
        help="wall limit of one phase, compilation included",
    )
    parser.add_argument(
        "--phase", choices=PHASES[:2], help=argparse.SUPPRESS
    )  # child entry
    args = parser.parse_args(argv)

    import go_ibft_tpu  # noqa: F401 - a bare copy of this file must fail here

    if args.phase:
        try:
            return _child_main(args)
        except Exception:  # noqa: BLE001 - the child's whole job is this report
            traceback.print_exc()
            return 1

    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        parser.error(f"unknown phase(s) {unknown}")
    from go_ibft_tpu import native

    # Build the native library once, here, so no two children race g++.
    native.load()
    # Every process this run starts must report this cache directory
    # (reading jax.config initializes no backend).
    from go_ibft_tpu.utils.jaxcache import resolve_cache_dir

    cache_dir = resolve_cache_dir()
    device = None
    try:
        for phase in phases:
            if phase == "node":
                line = asyncio.run(
                    node_phase(
                        args.validators,
                        args.heights,
                        args.corrupt,
                        args.seed,
                        boot_timeout_s=args.phase_timeout,
                    )
                )
                line = {"phase": "node", "ok": True, **line}
            else:
                line = _run_child(phase, args)
            print(json.dumps(line), flush=True)
            _check(
                line["cache_dir"] == cache_dir,
                f"phase {phase} cached its programs in {line['cache_dir']!r}, "
                f"not {cache_dir!r}",
            )
            found = {k: line[k] for k in ("platform", "kind", "count")}
            _check(
                found["platform"] == "tpu",
                f"phase {phase} ran on {found['platform']!r}",
            )
            _check(
                device is None or device == found,
                f"phase {phase} saw {found}, an earlier phase {device}",
            )
            device = found
        from jax._src import xla_bridge

        _check(
            not xla_bridge.backends_are_initialized(),
            "the smoke's parent initialized a JAX backend",
        )
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr, flush=True)
        return getattr(err, "rc", None) or 1
    except Exception:  # noqa: BLE001 - any crash is a failed smoke, loudly
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
