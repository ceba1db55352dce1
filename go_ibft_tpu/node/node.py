"""The deployable validator process (ISSUE 19).

:class:`ValidatorNode` composes every layer this repo has grown into ONE
process behind ONE config file:

* consensus   — ``core.IBFT`` + ``crypto.ECDSABackend`` gossiping over
  real TCP sockets (``net.GrpcTransport`` with peer reconnect), ingress
  batched through ``core.BatchingIngress``;
* persistence — ``chain.ChainRunner`` + ``chain.WriteAheadLog`` in
  ``data_dir``; boot always runs ``recover()`` (an empty WAL replays to
  genesis), so a restart resumes mid-round locks instead of
  double-signing;
* QoS         — one ``sched.TenantScheduler`` with the chain on the
  ``consensus`` tier and proof serving on the ``read`` tier, so client
  floods shed before a live round starves;
* serving     — ``serve.ProofServer`` exposed to untrusted clients over
  the :mod:`proof_api` wire transport;
* telemetry   — ``obs.httpd.TelemetryServer`` with /metrics, /healthz
  (liveness), /readyz (readiness: recovered + first height finalized),
  /statusz (scheduler + proof-API stats mounted);
* drain       — SIGTERM/SIGINT runs one graceful shutdown: stop taking
  proof clients, stop the height loop, stop the scheduler, fsync+close
  the WAL, export the per-node trace file, close the gossip listener.
  The trace export is what ``scripts/consensus_timeline.py`` merges
  into the cross-process timeline.

Lifecycle (the __main__ entry drives this)::

    node = ValidatorNode(load_config("node.toml"))
    report = asyncio.run(node.run())   # returns the drain report dict
"""

from __future__ import annotations

import asyncio
import os
import signal
import time
from typing import Optional

from ..chain import ChainRunner, WriteAheadLog
from ..core import IBFT, BatchingIngress
from ..core.validator_manager import calculate_quorum
from ..crypto import PrivateKey
from ..crypto.backend import ECDSABackend
from ..net import GrpcTransport
from ..obs import ledger as cost_ledger
from ..obs import trace
from ..utils import metrics
from ..verify import HostBatchVerifier
from .config import NodeConfig, NodeConfigError
from .proof_api import ProofApiServer

__all__ = ["ValidatorNode", "build_block_fn"]


class _NullLogger:
    def info(self, *a):
        pass

    debug = info

    def error(self, msg, *args):
        import sys

        print(f"[node error] {msg} {args}", file=sys.stderr, flush=True)


def build_block_fn(node_id: int):
    """The node's block builder: deterministic bytes per height.

    Every validator must build the IDENTICAL proposal for a height (the
    reference's ``Backend.BuildProposal`` determinism assumption in this
    payload-free reproduction), so the builder keys on the height alone —
    ``node_id`` rides along only for error messages."""
    del node_id

    def build(view) -> bytes:
        return b"fleet block %d" % view.height

    return build


def votes_to_quorum(powers, own: bytes) -> int:
    """The fewest OTHER validators whose votes, with ``own``'s, reach the
    quorum: ``quorum - 1`` where power is equal."""
    need = calculate_quorum(sum(powers.values())) - powers[own]
    votes = 0
    for power in sorted((p for a, p in powers.items() if a != own), reverse=True):
        if need <= 0:
            break
        need -= power
        votes += 1
    return votes


class ValidatorNode:
    """One validator process: see the module docstring.

    Construction wires everything but opens no sockets; :meth:`run`
    owns the lifecycle.  ``install_signal_handlers=False`` lets an embedder
    (a test, the benchmark's harness) run a node in a process that keeps
    its own handlers; :meth:`request_stop` is then what SIGTERM is to the
    binary.
    """

    def __init__(
        self,
        config: NodeConfig,
        *,
        logger=None,
        install_signal_handlers: bool = True,
    ) -> None:
        config.validate()
        self.config = config
        self._log = logger or _NullLogger()
        self._install_signals = install_signal_handlers
        os.makedirs(config.data_dir, exist_ok=True)

        if config.trace.enabled:
            trace.enable(config.trace.ring)

        # -- identity + validator set --------------------------------
        self.key = PrivateKey.from_seed(config.key_seed_bytes)
        powers = config.validator_powers()
        if self.key.address not in powers:
            raise NodeConfigError(
                f"node address {self.key.address.hex()} (from key_seed) is "
                f"not in [validators] — this process would gossip into a "
                f"committee that never counts it"
            )
        self.validators_src = ECDSABackend.static_validators(powers)

        # -- QoS scheduler -------------------------------------------
        self.scheduler = None
        batch_verifier = None
        if config.sched_enabled:
            from ..sched import TenantScheduler

            # Route per config ("host" default).  A device-route flush at
            # an unwarmed shape would park the flush thread inside an XLA
            # compile — wedging live rounds, the proof API's read tier AND
            # scheduler.stop() during drain — so the device path is opt-in
            # and every shape this committee can dispatch is compiled at
            # boot (run(), below).
            self.scheduler = TenantScheduler(route=config.sched_route)
            batch_verifier = self.scheduler.register(
                f"node{config.node_id}/consensus",
                self.validators_src,
                chain_id=f"node{config.node_id}",
            )
        else:
            batch_verifier = HostBatchVerifier(self.validators_src)

        # -- engine + transport --------------------------------------
        backend = ECDSABackend(
            self.key,
            self.validators_src,
            build_proposal_fn=build_block_fn(config.node_id),
        )
        self.engine = IBFT(
            self._log, backend, None, batch_verifier=batch_verifier
        )
        self.engine.set_base_round_timeout(config.consensus.base_round_timeout_s)
        # Votes arrive one RPC at a time.  Where a flush can reach the device
        # the ingress holds them until enough are in to decide (a quorum with
        # this node's own vote) or the sockets go quiet; the host route
        # verifies as they come, beside the arrivals.
        self.ingress = BatchingIngress(
            self.engine.add_messages,
            burst_hint=(
                votes_to_quorum(powers, self.key.address)
                if self.scheduler is not None and config.sched_route != "host"
                else None
            ),
        )
        self.transport = GrpcTransport(
            config.consensus.listen,
            config.consensus.peers,
            self.ingress.submit,
            logger=self._log,
            node=self.engine._obs_track,
            reconnect_after=config.consensus.reconnect_after,
        )
        self.engine.transport = self.transport

        # -- chain + WAL ---------------------------------------------
        self.wal_path = os.path.join(config.data_dir, "wal.jsonl")
        self.runner = ChainRunner(
            self.engine,
            WriteAheadLog(self.wal_path),
            overlap=False,  # single-chain node: overlap buys nothing here
        )

        # -- serve plane ---------------------------------------------
        self.proof_api: Optional[ProofApiServer] = None
        self._proof_server = None
        if config.proof_api.listen:
            from ..serve import ProofBuilder, ProofCache, ProofServer

            host, _, port = config.proof_api.listen.rpartition(":")
            self._proof_server = ProofServer(
                ProofBuilder(self.runner, self.runner.validators_for_height),
                ProofCache(),
                scheduler=self.scheduler,
                max_proof_heights=config.proof_api.max_proof_heights,
            )
            self.proof_api = ProofApiServer(
                self._proof_server,
                self.runner.latest_height,
                host=host or "127.0.0.1",
                port=int(port),
                max_connections=config.proof_api.max_connections,
                max_request_bytes=config.proof_api.max_request_bytes,
                header_timeout_s=config.proof_api.header_timeout_s,
                idle_timeout_s=config.proof_api.idle_timeout_s,
                workers=config.proof_api.workers,
                ready_fn=self.runner.telemetry_ready,
            )

        self.telemetry = None
        self._ledger_owned = False
        self._drained = False
        self._stop_requested: Optional[asyncio.Event] = None
        self._started_at = time.monotonic()

    # -- lifecycle ------------------------------------------------------

    async def run(self) -> dict:
        """Boot, serve, run the chain, drain; returns the drain report."""
        cfg = self.config
        loop = asyncio.get_running_loop()
        stop_requested = self._stop_requested = asyncio.Event()
        if self._install_signals:
            for sig in (signal.SIGTERM, signal.SIGINT):
                loop.add_signal_handler(sig, stop_requested.set)

        if cfg.telemetry.listen and not cost_ledger.enabled():
            # The telemetry mount serves the cost ledger (/statusz totals,
            # /metrics families): turn it on BEFORE the warm-up so boot
            # compiles are on the record and "compiles after boot" reads
            # straight off a scrape.  Process-global, so the drain turns
            # off what this node turned on.
            cost_ledger.enable()
            self._ledger_owned = True
        if self.scheduler is not None:
            self.scheduler.start()
            if cfg.sched_route != "host":
                # Compile NOW, while /readyz is still 503, every (lane
                # bucket, table bucket) this committee can make the
                # dispatcher launch — derived from the validator count, so
                # a 100-validator node never meets its first 128-lane
                # program on the flush thread mid-round — and only those:
                # each ladder program is 7-15 s of boot from a warm cache.
                # The proof API's read tier coalesces up to a full dispatch,
                # so with it every width is loaded.
                self.scheduler.warmup_committee(
                    len(cfg.validators), read_tier=self.proof_api is not None
                )
        await self.transport.start()
        bound_consensus = self.transport.bound_port

        # Recover BEFORE anything is routable: /readyz stays 503 until
        # this returns (the supervisor contract).
        resumed_at = self.runner.recover()

        if cfg.telemetry.listen:
            host, _, port = cfg.telemetry.listen.rpartition(":")
            extra = {}
            if self.scheduler is not None:
                extra["sched"] = self.scheduler.stats
            if self.proof_api is not None:
                extra["proof_api"] = self.proof_api.stats
            self.telemetry = self.runner.start_telemetry(
                port=int(port),
                host=host or "127.0.0.1",
                wedged_after_s=cfg.telemetry.wedged_after_s or None,
                extra_status=extra,
            )
        if self.proof_api is not None:
            self.proof_api.start()

        self._emit_boot_line(bound_consensus, resumed_at)

        chain_task = asyncio.create_task(
            self.runner.run(
                until_height=cfg.heights if cfg.heights > 0 else None
            ),
            name="node-chain",
        )
        stop_task = asyncio.create_task(
            stop_requested.wait(), name="node-stop"
        )
        try:
            done, _pending = await asyncio.wait(
                {chain_task, stop_task},
                return_when=asyncio.FIRST_COMPLETED,
            )
            if chain_task in done:
                chain_task.result()  # surface a crashed height loop
        finally:
            stop_task.cancel()
            report = await self._drain(chain_task)
        return report

    def request_stop(self) -> None:
        """Ask :meth:`run` for the graceful drain a SIGTERM asks for (call
        it from the loop ``run`` is on; nothing to stop before ``run``)."""
        if self._stop_requested is not None:
            self._stop_requested.set()

    async def _drain(self, chain_task: Optional[asyncio.Task]) -> dict:
        """Graceful shutdown, in dependency order (see module docstring)."""
        if self._drained:
            return {}
        self._drained = True
        cfg = self.config
        # 1. Stop taking new proof clients (drop the fleet first: nothing
        # downstream depends on them).
        if self.proof_api is not None:
            self.proof_api.stop()
        # 2. Stop the height loop; in-flight WAL appends complete under
        # the WAL lock before close().
        if chain_task is not None and not chain_task.done():
            chain_task.cancel()
            await asyncio.gather(chain_task, return_exceptions=True)
        # 3. Scheduler: drain queued verification, stop the loop thread.
        if self.scheduler is not None:
            self.scheduler.stop()
        if self._proof_server is not None:
            self._proof_server.close()
        # 4. WAL: fsync + close — after this a SIGKILL loses nothing.
        if self.runner.wal is not None:
            self.runner.wal.close()
        # 5. Trace export for the cross-process timeline.
        trace_path = None
        trace_events = 0
        if cfg.trace.enabled:
            trace_path = os.path.join(
                cfg.data_dir, f"trace-node{cfg.node_id}.json"
            )
            try:
                trace_events = self.runner.export_trace(trace_path)
            except Exception as err:  # noqa: BLE001 - drain must finish
                self._log.error("trace export failed", err)
                trace_path = None
        # 6. Close listeners: gossip + telemetry go last so peers see our
        # final COMMITs and a supervisor can scrape the drain.
        await self.transport.stop()
        if self.telemetry is not None:
            self.runner.stop_telemetry()
        if self._ledger_owned:
            cost_ledger.disable()
            self._ledger_owned = False
        self.ingress.close()
        self.engine.messages.close()
        speculator = getattr(self.engine, "speculator", None)
        if speculator is not None:
            speculator.stop()
        report = self._report(trace_path, trace_events)
        return report

    # -- evidence -------------------------------------------------------

    def _emit_boot_line(self, consensus_port, resumed_at: int) -> None:
        """One JSON line on stdout the harness parses for bound ports."""
        import json

        line = {
            "node_boot": self.config.node_id,
            "address": self.key.address.hex(),
            "consensus_port": consensus_port,
            "proof_api_port": (
                self.proof_api.port if self.proof_api is not None else None
            ),
            "telemetry_port": (
                self.telemetry.port if self.telemetry is not None else None
            ),
            "resumed_at_height": resumed_at,
        }
        if self.scheduler is not None and self.config.sched_route != "host":
            # The device the boot warm-up compiled for, as JAX reports it,
            # and where those programs are cached.  Never on the host
            # route: asking would initialize a backend, and a node that
            # dispatches nothing must not claim a chip.
            import jax

            from ..utils.jaxcache import resolve_cache_dir

            devices = jax.devices()
            line["device"] = {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
            }
            line["compile_cache"] = resolve_cache_dir()
        print(json.dumps(line), flush=True)

    def _report(self, trace_path, trace_events: int) -> dict:
        stats = self.runner.stats()
        return {
            "node": self.config.node_id,
            "address": self.key.address.hex(),
            "chain_height": self.runner.latest_height(),
            "heights_run": stats["heights_run"],
            "wal_path": self.wal_path,
            "trace_path": trace_path,
            "trace_events": trace_events,
            "proof_api": (
                self.proof_api.stats() if self.proof_api is not None else None
            ),
            "sched": (
                self.scheduler.stats() if self.scheduler is not None else None
            ),
            "send_failures": metrics.get_counter(
                ("go-ibft", "transport", "send_failures")
            ),
            "peer_reconnects": metrics.get_counter(
                ("go-ibft", "transport", "peer_reconnects")
            ),
            "uptime_s": round(time.monotonic() - self._started_at, 3),
        }
