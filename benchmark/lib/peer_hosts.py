"""The other validators of a node under test, on real sockets (host only:
never imports jax).

Driver ``node`` embeds one ``ValidatorNode`` in the harness process; its
``n - 1`` peers live here, in a few spawned processes.  Each peer has what a
validator has on the wire: ONE listener of its own, which the node's
``GrpcTransport`` multicasts to over that peer's own channel, and ONE channel
to the node's listener (the service ``net/grpc_transport.py`` registers:
unary ``/goibft.Transport/Multicast``, the message's wire bytes as the
request).  A peer is no engine: its messages were signed before the window
(``Committee.traffic``), and it sends its PREPARE when ITS listener receives
the node's PREPARE (the node's PREPREPARE where the node proposes) and its
COMMIT when it receives the node's COMMIT, so the arrivals interleave with
the node's own sends as they do on a network.  The proposer's PREPREPARE
goes out when the driver says so.  A mix may name ``silent_peers``: that many
of the peers (the highest indices) propose in their turn and vote never, as
a validator whose votes are lost does.

Every send is stamped on ``time.perf_counter`` (CLOCK_MONOTONIC on Linux,
one clock for every process of the machine), so the driver can put the
client's side of a hop beside the node's.
"""

from __future__ import annotations

import asyncio
import multiprocessing
import os
import pickle
import sys
import time
from typing import Dict, List, Optional, Sequence

from go_ibft_tpu.messages.wire import IbftMessage, MessageType, decode_traced

from .stats import median

FULL_METHOD = "/goibft.Transport/Multicast"  # net/grpc_transport.py's
SERVICE, METHOD = "goibft.Transport", "Multicast"


def _identity(b: bytes) -> bytes:
    return b


def split(peers: Sequence[int], hosts: int) -> List[List[int]]:
    """``peers`` (validator indices) dealt to ``hosts`` processes in runs as
    even as they come: 99 over four is 25 / 25 / 25 / 24."""
    size, extra = divmod(len(peers), hosts)
    out, at = [], 0
    for i in range(hosts):
        n = size + (1 if i < extra else 0)
        out.append(list(peers[at : at + n]))
        at += n
    return [p for p in out if p]


class _Host:
    """One process's peers: their listeners, channels and signed messages."""

    def __init__(
        self, conn, n: int, seed: int, mine: Sequence[int], silent: int = 0
    ) -> None:
        from .committee import Committee

        self.conn = conn
        self.committee = Committee(n, seed)
        self.mine = {self.committee.keys[i].address: i for i in mine}
        self.voters = {a for a, i in self.mine.items() if i < n - silent}
        self.node = self.committee.node
        self.servers: list = []
        self.channels: list = []
        self.stubs: Dict[bytes, object] = {}
        # (height, address) -> wire bytes; the PREPREPAREs by height alone.
        self.prepares: Dict[tuple, bytes] = {}
        self.commits: Dict[tuple, bytes] = {}
        self.preprepares: Dict[int, tuple] = {}
        self.answered: set = set()
        self.sends: set = set()
        self.sent = self.acked = self.send_errors = self.heard = 0
        self.preprepare_sent_at: Dict[int, float] = {}
        self.stopping = asyncio.Event()

    # -- the wire -------------------------------------------------------

    async def listen(self) -> Dict[int, int]:
        """One gRPC server a peer, each on a port of its own."""
        import grpc

        ports = {}
        for addr, index in self.mine.items():
            server = grpc.aio.server()

            async def handle(request: bytes, context, addr=addr) -> bytes:
                self.on_message(addr, request)
                return b""

            server.add_generic_rpc_handlers(
                (
                    grpc.method_handlers_generic_handler(
                        SERVICE,
                        {
                            METHOD: grpc.unary_unary_rpc_method_handler(
                                handle,
                                request_deserializer=_identity,
                                response_serializer=_identity,
                            )
                        },
                    ),
                )
            )
            ports[index] = server.add_insecure_port("127.0.0.1:0")
            await server.start()
            self.servers.append(server)
        return ports

    def connect(self, target: str) -> None:
        """One channel a peer to the node's listener."""
        import grpc

        for addr in self.mine:
            channel = grpc.aio.insecure_channel(target)
            self.channels.append(channel)
            self.stubs[addr] = channel.unary_unary(
                FULL_METHOD,
                request_serializer=_identity,
                response_deserializer=_identity,
            )

    def on_message(self, addr: bytes, request: bytes) -> None:
        """The node's multicast as this peer's listener got it: answer a
        PREPARE (or the node's own PREPREPARE) with this peer's PREPARE, a
        COMMIT with its COMMIT, once each."""
        self.heard += 1
        raw, _ctx = decode_traced(request)
        message = IbftMessage.decode(raw)
        if message.sender != self.node or message.view is None or message.view.round:
            return
        height = message.view.height
        if message.type in (MessageType.PREPREPARE, MessageType.PREPARE):
            key, payload = ("prepare", height, addr), self.prepares.get((height, addr))
        elif message.type == MessageType.COMMIT:
            key, payload = ("commit", height, addr), self.commits.get((height, addr))
        else:
            return
        if payload is None or key in self.answered:
            return
        self.answered.add(key)
        self.send(addr, payload)

    def send(self, addr: bytes, payload: bytes) -> None:
        task = asyncio.get_running_loop().create_task(self._send(addr, payload))
        self.sends.add(task)
        task.add_done_callback(self.sends.discard)

    async def _send(self, addr: bytes, payload: bytes) -> None:
        self.sent += 1
        try:
            # No deadline: the node's loop may be held for a minute and more
            # (a traced run's ``stop_trace``) with this RPC in its queue.
            await self.stubs[addr](payload)
            self.acked += 1
        except Exception:  # noqa: BLE001 - counted; the run is then incorrect
            self.send_errors += 1

    # -- the driver's side ----------------------------------------------

    def load(self, blobs: Sequence[bytes]) -> int:
        """This host's share of pre-signed heights, as wire bytes."""
        for blob in blobs:
            t = pickle.loads(blob)
            h = t.height
            for m in t.prepares:
                if m.sender in self.voters:
                    self.prepares[(h, m.sender)] = m.encode()
            for m in t.commits:
                if m.sender in self.voters:
                    self.commits[(h, m.sender)] = m.encode()
            if t.preprepare is not None and t.proposer in self.mine:
                self.preprepares[h] = (t.proposer, t.preprepare.encode())
        return len(self.commits)

    def on_command(self) -> None:
        try:
            command, arg = self.conn.recv()
        except EOFError:
            self.stopping.set()
            return
        if command == "preprepare":
            addr, payload = self.preprepares[arg]
            self.preprepare_sent_at[arg] = time.perf_counter()
            self.send(addr, payload)
        elif command == "load":
            self.conn.send(("loaded", self.load(arg)))
        elif command == "connect":
            self.connect(arg)
            self.conn.send(("connected", len(self.stubs)))
        elif command == "report":
            asyncio.get_running_loop().create_task(self.report())
        elif command == "stop":
            self.stopping.set()

    async def report(self) -> None:
        """What this host sent, once nothing of it is in flight."""
        while self.sends:
            await asyncio.gather(*list(self.sends), return_exceptions=True)
        self.conn.send(
            (
                "report",
                {
                    "sent": self.sent,
                    "acked": self.acked,
                    "send_errors": self.send_errors,
                    "heard": self.heard,
                    "preprepare_sent_at": dict(self.preprepare_sent_at),
                    "jax": "jax" in sys.modules,
                    "native": bool(self.committee.native),
                },
            )
        )

    async def run(self) -> None:
        ports = await self.listen()
        loop = asyncio.get_running_loop()
        loop.add_reader(self.conn.fileno(), self.on_command)
        self.conn.send(("ports", ports))
        await self.stopping.wait()
        loop.remove_reader(self.conn.fileno())
        for channel in self.channels:
            await channel.close()
        for server in self.servers:
            await server.stop(grace=0.1)


def host_main(conn, n: int, seed: int, mine: Sequence[int], silent: int) -> None:
    """A peer-host process (started with ``spawn``: a fresh interpreter)."""
    asyncio.run(_Host(conn, n, seed, mine, silent).run())


class PeerHosts:
    """The driver's handle on the peer-host processes."""

    def __init__(self, n: int, seed: int, hosts: int, silent: int = 0) -> None:
        ctx = multiprocessing.get_context("spawn")
        self.shares = split(list(range(1, n)), hosts)
        self.conns, self.procs = [], []
        self.host_of: Dict[int, int] = {}
        # The children never import jax; should one ever, it is held to the
        # CPU: the chip belongs to the harness process.
        before = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for k, mine in enumerate(self.shares):
                here, there = ctx.Pipe()
                proc = ctx.Process(
                    target=host_main, args=(there, n, seed, mine, silent), daemon=True
                )
                proc.start()
                there.close()
                self.conns.append(here)
                self.procs.append(proc)
                for index in mine:
                    self.host_of[index] = k
        finally:
            if before is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = before
        self.ports: Dict[int, int] = {}

    def _expect(self, conn, what: str, timeout_s: float = 120.0):
        if not conn.poll(timeout_s):
            raise RuntimeError(f"a peer host did not answer {what!r} in {timeout_s:.0f}s")
        got, value = conn.recv()
        if got != what:
            raise RuntimeError(f"a peer host answered {got!r}, not {what!r}")
        return value

    def listen(self) -> Dict[int, int]:
        """Every peer's listener port, by validator index."""
        for conn in self.conns:
            self.ports.update(self._expect(conn, "ports"))
        return self.ports

    def load(self, blobs: Sequence[bytes]) -> int:
        """Hand every host the pre-signed heights; each keeps its peers'."""
        for conn in self.conns:
            conn.send(("load", list(blobs)))
        return sum(self._expect(conn, "loaded") for conn in self.conns)

    def connect(self, target: str) -> int:
        for conn in self.conns:
            conn.send(("connect", target))
        return sum(self._expect(conn, "connected") for conn in self.conns)

    def preprepare(self, height: int, proposer_index: int) -> None:
        self.conns[self.host_of[proposer_index]].send(("preprepare", height))

    def report(self) -> List[dict]:
        for conn in self.conns:
            conn.send(("report", None))
        return [self._expect(conn, "report") for conn in self.conns]

    def close(self) -> None:
        for conn in self.conns:
            try:
                conn.send(("stop", None))
            except (OSError, ValueError):
                pass
        for proc in self.procs:
            proc.join(timeout=10.0)
            if proc.is_alive():
                proc.kill()
                proc.join()
        for conn in self.conns:
            conn.close()
        self.conns, self.procs = [], []


def median_hop_ms(
    sent_at: Dict[int, float], arrived_at: Dict[int, float]
) -> Optional[float]:
    """Median, in ms, of arrival - send over the heights both sides stamped."""
    hops = [(arrived_at[h] - t) * 1e3 for h, t in sent_at.items() if h in arrived_at]
    return median(hops) if hops else None
