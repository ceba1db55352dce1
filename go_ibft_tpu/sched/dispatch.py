"""The multi-tenant coalesced verify data plane.

One flush of the :class:`~go_ibft_tpu.sched.scheduler.TenantScheduler`
carries lanes from MANY chains — different validator sets, different
proposal hashes, different heights — and must still land on the device as
ONE batched dispatch.  The trick that makes cross-tenant coalescing exact
is splitting the verification predicate at the membership check:

* **signature validity is chain-agnostic** — ``recover(digest, sig) ==
  claimed address`` does not mention a validator set, so lanes from any
  number of chains share one recovery-ladder launch;
* **membership is a host dict lookup** — ``claimed in
  tenant.validators(height)`` is exact Python over the tenant's own
  voting-power map, applied per lane after the shared mask returns.

The device dispatch therefore runs the EXISTING pinned programs
(:data:`DIGEST_KERNEL` / :data:`RECOVER_KERNEL` are the very jit objects
``verify/batch.py`` compiled for the single-tenant plane — asserted by
``scripts/compile_budget.py``, so the shared plane can never fork a new
program family) with the membership table packed from the lanes' own
claimed addresses: every live lane's claimed address is trivially a table
member, which reduces the kernel's mask to pure signature validity.  The
per-tenant membership AND happens on host, so the final verdict per lane
is bit-identical to that tenant's sequential
:class:`~go_ibft_tpu.verify.batch.HostBatchVerifier` oracle.

The host route does the same split over the native bulk verifier (one
GIL-releasing C call for the whole coalesced flush) or, without the
native library, the pure-Python recover loop — the scheduler picks per
flush exactly like :class:`~go_ibft_tpu.verify.batch.AdaptiveBatchVerifier`
picks per drain (measured cutover, small flushes stay on host).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..crypto import ecdsa as host_ecdsa
from ..crypto.keccak import keccak256_many
from ..messages.helpers import CommittedSeal
from ..messages.wire import IbftMessage
from ..obs import ledger as cost_ledger
from ..obs import trace
from ..utils import metrics
from ..verify import batch as vbatch
from ..verify.batch import (
    ADDRESS_BYTES,
    SIG_BYTES,
    _BATCH_BUCKETS,
    _TABLE_BUCKETS,
    _bucket,
    pack_seal_lanes,
    pack_validator_table,
)
from ..verify.pipeline import PackCache, SenderRows

__all__ = [
    "CoalescedDispatcher",
    "DIGEST_KERNEL",
    "RECOVER_KERNEL",
    "DISPATCH_LANES_KEY",
    "DISPATCH_MS_KEY",
    "SERVED_KEY",
    "TABLE_CUTS_KEY",
    "UNPINNED_LAUNCHES_KEY",
]

# The shared dispatch MUST reuse the single-tenant plane's compiled
# programs — these are the same jit objects, not re-jitted copies
# (scripts/compile_budget.py asserts the identity so a refactor that
# forks a new program family fails CI, and docs/compile_budget.json
# gains no sched entries).
DIGEST_KERNEL = vbatch._digest_kernel
RECOVER_KERNEL = vbatch._recover_kernel

DISPATCH_LANES_KEY = ("go-ibft", "sched", "dispatch_lanes")
DISPATCH_MS_KEY = ("go-ibft", "sched", "dispatch_ms")
# What served the flushes, as counters a scrape and the benchmark both read:
# ``SERVED_KEY + (<route>/<padded lanes>,)`` a kernel launch (``host``: a
# flush), the launches at a ``(lanes, rows)`` shape boot had pinned and did
# not load (0: the rule ``_launch`` keeps), and the launches ``_launch`` cut
# in two because their signers outgrew the pinned table rows.
SERVED_KEY = ("go-ibft", "sched", "served")
UNPINNED_LAUNCHES_KEY = ("go-ibft", "sched", "unpinned_launches")
TABLE_CUTS_KEY = ("go-ibft", "sched", "table_cuts")


class _RoutingPackCache:
    """Store-side shim routing ``pack_sender_batch`` cache stores to each
    message's OWN tenant cache.

    A coalesced sender pack mixes messages from many tenants, but
    ``pack_sender_batch`` takes one ``cache`` to store fresh packs into.
    Lookups are supplied pre-routed (``cache_hits``); this shim routes the
    pack's one by-reference store by message identity, one call per tenant
    in it, so one tenant's packs can never land in — or later be served
    from — another tenant's cache (the namespacing contract of
    docs/TENANCY.md)."""

    def __init__(self, owners: Dict[int, PackCache]):
        self._owners = owners

    def store_rows(self, msgs, rows: SenderRows, at) -> None:
        by_owner: Dict[int, tuple] = {}
        for msg, row in zip(msgs, at):
            owner = self._owners.get(id(msg))
            if owner is not None:
                mine = by_owner.setdefault(id(owner), (owner, [], []))
                mine[1].append(msg)
                mine[2].append(row)
        for owner, mine, rows_at in by_owner.values():
            owner.store_rows(mine, rows, rows_at)


def well_formed_sender(msg: IbftMessage) -> bool:
    """The oracle's sender-lane admission predicate
    (:meth:`HostBatchVerifier.verify_senders` skip conditions)."""
    return (
        msg.view is not None
        and len(msg.sender) == ADDRESS_BYTES
        and len(msg.signature) == SIG_BYTES
    )


def well_formed_seal_lane(proposal_hash: bytes, seal: CommittedSeal) -> bool:
    """The oracle's seal-lane admission predicate (hash + signer + sig)."""
    return (
        len(proposal_hash) == 32
        and len(seal.signer) == ADDRESS_BYTES
        and len(seal.signature) == SIG_BYTES
    )


_KINDS = ("senders", "seal_lanes")


class _Launched:
    """One flush between its two halves (:meth:`CoalescedDispatcher.launch`,
    :meth:`CoalescedDispatcher.collect`): its open ``sched.dispatch`` span, and what is still to be read back, by kind in
    launch order as ``(the kernel's result, live lanes)``; ``host``: the host
    route's masks, which were ready when the launch returned (``ready``:
    nothing is left to wait for)."""

    __slots__ = ("total", "span", "t0", "parts", "host")

    def __init__(self, total: int, span) -> None:
        self.total = total
        self.span = span
        self.t0 = time.perf_counter()
        self.parts: Dict[str, list] = {kind: [] for kind in _KINDS}
        self.host: Optional[Tuple[np.ndarray, np.ndarray]] = None

    @property
    def ready(self) -> bool:
        return self.host is not None


class CoalescedDispatcher:
    """One shared pack/dispatch engine for mixed-tenant lane batches.

    :meth:`dispatch` takes pre-filtered (well-formed) sender messages and
    ``(proposal_hash, seal)`` lanes from any mix of tenants and returns
    one *signature-validity* mask per kind — ``recover(digest) ==
    claimed``; membership is the caller's (per-tenant, host-exact).

    ``route``:

    * ``"auto"`` — host below the measured adaptive cutover (the same
      calibration the :class:`AdaptiveBatchVerifier` uses: a handful of
      lanes never pays a device dispatch floor), device at or above it;
    * ``"host"`` / ``"device"`` — forced (bench variants, tests).

    ``mesh`` / ``dp`` / ``devices`` (live mesh resize, ISSUE 16): when a
    multi-device mesh resolves (``mesh`` wins; else ``dp``/``devices``
    re-enter through :func:`~go_ibft_tpu.parallel.mesh.mesh_context`),
    the device route dispatches the lane-sharded
    :func:`~go_ibft_tpu.verify.mesh_batch.mesh_verify_mask` program —
    the SAME pinned ``mesh_verify_mask_*_dp*`` family the single-tenant
    sharded verifier compiled — with lanes pinned to ``bucket x dp`` so
    every shard sees an identical local shape (pad lanes are dead).  A
    1-device resolution degrades to the single-device kernels exactly.
    The scheduler swaps whole dispatchers to resize
    (:meth:`TenantScheduler.reconfigure`), so this state is immutable.
    """

    def __init__(
        self,
        route: str = "auto",
        cutover_lanes: Optional[int] = None,
        *,
        mesh=None,
        dp: Optional[int] = None,
        devices=None,
    ):
        if route not in ("auto", "host", "device"):
            raise ValueError(f"unknown route {route!r}")
        self.route = route
        if route != "host":
            # Like DeviceBatchVerifier: whoever can launch the ladder
            # programs keeps them in the persistent cache, so a node's
            # second boot loads its warm-up instead of compiling it.
            from ..utils.jaxcache import enable_persistent_cache

            enable_persistent_cache()
        if cutover_lanes is None:
            from ..utils import calibration

            cutover_lanes = (
                calibration.measured_cutover()
                or calibration.DEFAULT_CUTOVER_LANES
            )
        self.cutover = cutover_lanes
        self.mesh = None
        self.dp = 1
        self._mask_kernel = None
        if mesh is None and (dp is not None or devices is not None):
            from ..parallel.mesh import mesh_context

            mesh = mesh_context(dp, devices=devices)
        if mesh is not None and mesh.devices.size >= 2:
            from ..verify.mesh_batch import mesh_verify_mask

            self.mesh = mesh
            self.dp = int(np.prod(mesh.devices.shape))
            self._mask_kernel = mesh_verify_mask(mesh)
        # The recover programs compile per lane bucket; serialize warmup.
        self._warm_lock = threading.Lock()
        # What actually served the flushes: kernel launches per
        # ``<route>/<padded lanes>`` and host flushes under ``host`` —
        # counted where the work runs, so /statusz can show a device-route
        # node really dispatching (and at which bucket), not a setting.
        self._served_lock = threading.Lock()
        self._served: Dict[str, int] = {}
        # The recover widths the device route launches, ascending, and the
        # table rows each is launched with where boot fixed them
        # (:meth:`warmup_committee`): a kind's lanes go out in chunks of at
        # most the widest, each at the narrowest that holds it, so a flush of
        # ANY size lands on a program boot loaded.  Every bucket until then.
        self._rungs: Tuple[int, ...] = _BATCH_BUCKETS
        self._rung_tables: Dict[int, int] = {}

    def describe(self) -> dict:
        """Shape of this dispatcher (scheduler stats / resize evidence)."""
        return {
            "route": self.route,
            "dp": self.dp,
            "sharded": self.mesh is not None,
            "cutover": self.cutover,
            # The recover widths the device route launches (every bucket
            # until ``warmup_committee`` loaded the committee's).
            "widths": list(self._rungs),
            # ... as ``[lanes, table rows]`` once boot pinned them.
            "shapes": [list(shape) for shape in sorted(self._rung_tables.items())],
        }

    def served(self) -> Dict[str, int]:
        """Launch counts by ``<route>/<padded lanes>`` (``host``: flushes)."""
        with self._served_lock:
            return dict(self._served)

    def _note_served(self, key: str) -> None:
        with self._served_lock:
            self._served[key] = self._served.get(key, 0) + 1
        metrics.inc_counter(SERVED_KEY + (key,))

    def _pad_lanes(self, n: int) -> int:
        """Mesh dispatches pin the lane dim to ``bucket(ceil(n/dp)) x dp``
        (every shard gets an identical local shape; pad lanes are dead);
        single-device dispatches go out at the narrowest of ``_rungs`` that
        holds them (the pack functions' own bucket until boot chose fewer)."""
        if n == 0:
            return 0
        if self.mesh is None:
            return _bucket(n, self._rungs)
        return _bucket((n + self.dp - 1) // self.dp, _BATCH_BUCKETS) * self.dp

    def _table(self, addresses: List[bytes], lanes: int) -> np.ndarray:
        """The claimed-address table of one launch at ``lanes`` wide, padded
        (row 0 again, as its own bucket pads: no new member) to the rows boot
        loaded that width with.  :meth:`_launch` hands over no more signers
        than those rows hold."""
        table = pack_validator_table(addresses)
        rows = self._rung_tables.get(lanes, 0)
        if rows <= table.shape[0]:
            return table
        return np.concatenate(
            [table, np.broadcast_to(table[0], (rows - table.shape[0], 5))]
        )

    # -- public ----------------------------------------------------------

    def warmup(self, lanes: Sequence[int] = (8,), table_rows: int = 8) -> None:
        """Pre-compile the shared kernels (node startup; never mid-round)."""
        import jax
        import jax.numpy as jnp

        with self._warm_lock:
            for bb in lanes:
                # Warm the kernel the device route will actually launch:
                # the sharded mask program at its dp-aligned global shape
                # when a mesh is attached, the single-device recover
                # ladder otherwise.
                gg = self._pad_lanes(bb) if self.mesh is not None else bb
                kernel, program, _route = self._program()
                # In both forms :meth:`_sig_mask` calls it: host rows, and
                # ``zw`` a host array (seal lanes) or the digest program's
                # output (senders); the ledger counts a form the compiled
                # call has not seen as a compile.
                hashes = np.zeros((gg, 8), np.uint32)
                for zw in (hashes, jnp.asarray(hashes)):
                    with cost_ledger.dispatch_span(
                        program,
                        route="warmup",
                        padded=gg,
                        kernels=((program, kernel),),
                        site="sched/dispatch.py:warmup",
                    ):
                        kernel(
                            zw,
                            np.zeros((gg, 20), np.int32),
                            np.zeros((gg, 20), np.int32),
                            np.zeros((gg,), np.int32),
                            np.zeros((gg, 5), np.uint32),
                            np.zeros((table_rows, 5), np.uint32),
                            np.zeros((gg,), bool),
                        ).block_until_ready()
                with cost_ledger.dispatch_span(
                    "digest_words",
                    route="warmup",
                    padded=bb,
                    kernels=(("digest_words", DIGEST_KERNEL),),
                    site="sched/dispatch.py:warmup",
                ):
                    jax.block_until_ready(
                        DIGEST_KERNEL(
                            np.zeros((bb, 2, 17, 2), np.uint32),
                            np.ones((bb,), np.int32),
                        )
                    )

    def committee_rungs(
        self, n_validators: int, read_tier: bool = False
    ) -> Tuple[int, ...]:
        """The recover widths ONE committee of ``n_validators`` can make this
        dispatcher launch.  The consensus tier flushes at most a phase's
        worth of lanes of one kind: every bucket up to the committee's own,
        and under ``route="auto"`` none that only a flush below the cutover
        would pick (the host serves those).  A read tier coalesces proof
        ranges up to a full dispatch: every bucket."""
        if read_tier or self.mesh is not None:
            return _BATCH_BUCKETS
        top = _bucket(min(n_validators, _BATCH_BUCKETS[-1]), _BATCH_BUCKETS)
        low = 0
        if self.route == "auto":
            low = _bucket(min(self.cutover, top), _BATCH_BUCKETS)
        return tuple(bb for bb in _BATCH_BUCKETS if low <= bb <= top)

    def tenant_rungs(
        self,
        committees: Sequence[int],
        max_lanes: int = _BATCH_BUCKETS[-1],
        read_tier: bool = False,
    ) -> Tuple[int, ...]:
        """The recover widths the tenants of ``committees`` (a validator
        count each) can make this dispatcher launch.  One tenant:
        :meth:`committee_rungs`.  Several share every flush, and a flush of
        theirs (catch-up ranges of many chains at once, a phase of each
        together) coalesces up to ``max_lanes``, the scheduler's dispatch
        cap: the ONE width that holds the cap, every tail padded to it.
        """
        if len(committees) == 1 or read_tier or self.mesh is not None:
            return self.committee_rungs(max(committees), read_tier)
        return (_bucket(min(max_lanes, _BATCH_BUCKETS[-1]), _BATCH_BUCKETS),)

    def warmup_tenants(
        self,
        committees: Sequence[int],
        signers: Optional[int] = None,
        max_lanes: int = _BATCH_BUCKETS[-1],
        read_tier: bool = False,
    ) -> None:
        """Pre-compile what the tenants of ``committees`` can make this
        dispatcher launch (:meth:`tenant_rungs`), each width with the
        membership table its fullest flush packs: one claimed address per
        lane, capped by ``signers``, the distinct validators of all the
        tenants together (their sum where not given).  Nothing else is
        launched from here on (:meth:`_launch`): a cached ladder executable
        costs 7-15 s of boot to load, a cold one most of a minute to
        compile, so a 100-validator committee loads two or three and not
        all six, and sixteen chains of 316 validators one."""
        if not committees:
            raise ValueError("no tenant to warm up for")
        if self.route == "host":
            return  # no flush reaches a device program
        if signers is None:
            signers = sum(committees)
        rungs = self.tenant_rungs(committees, max_lanes, read_tier)
        tables = {bb: _bucket(min(bb, signers), _TABLE_BUCKETS) for bb in rungs}
        for bb in rungs:
            self.warmup(lanes=(bb,), table_rows=tables[bb])
        if self.mesh is None:
            self._rungs, self._rung_tables = rungs, tables

    def warmup_committee(
        self, n_validators: int, read_tier: bool = False
    ) -> None:
        """:meth:`warmup_tenants` for the one committee of a validator's own
        chain (what ``ValidatorNode`` boots with)."""
        self.warmup_tenants((n_validators,), read_tier=read_tier)

    def dispatch(
        self,
        sender_msgs: Sequence[IbftMessage],
        seal_lanes: Sequence[Tuple[bytes, CommittedSeal]],
        pack_caches: Optional[Dict[int, PackCache]] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Coalesced signature-validity masks for one flush.

        ``pack_caches`` maps ``id(msg)`` to the owning tenant's
        :class:`PackCache` (lookups AND stores are routed per message).
        Returns ``(sender_sig_ok, seal_sig_ok)``; membership is NOT
        included — the scheduler ANDs each lane with its own tenant's
        validator set.

        A flush is two halves, and this is one after the other:
        :meth:`launch` (route, packs, tables, every kernel call) and
        :meth:`collect` (the blocking read-backs).  The scheduler's loop
        calls the halves itself, with the launch of the next flush between
        them.
        """
        return self.collect(self.launch(sender_msgs, seal_lanes, pack_caches))

    def launch(
        self,
        sender_msgs: Sequence[IbftMessage],
        seal_lanes: Sequence[Tuple[bytes, CommittedSeal]],
        pack_caches: Optional[Dict[int, PackCache]] = None,
    ) -> "_Launched":
        """The first half of :meth:`dispatch`: everything up to and
        including the flush's last kernel call (the route, each kind's chunks
        packed, their tables cut to the loaded rows, the cost-ledger rows),
        with nothing read back.  The host route has nothing to wait for: it
        does its work here.  Returns what :meth:`collect` takes, ONCE, on
        this dispatcher; ``sched.dispatch`` stays open between the two."""
        total = len(sender_msgs) + len(seal_lanes)
        route = self.route
        if route == "auto":
            route = "device" if total >= self.cutover else "host"
        flush = _Launched(
            total,
            trace.begin(
                "sched.dispatch",
                route=route,
                lanes=total,
                senders=len(sender_msgs),
                seals=len(seal_lanes),
            ),
        )
        try:
            if route == "device":
                self._device(sender_msgs, seal_lanes, pack_caches or {}, flush)
            else:
                # Host flushes pad nothing (occupancy 1.0); the device
                # route records per kernel launch inside _device where
                # the padded bucket shapes are known.
                with cost_ledger.dispatch_span(
                    "ecdsa_recover",
                    route="host",
                    live=total,
                    padded=total,
                    site="sched/dispatch.py:dispatch",
                ):
                    flush.host = self._host(
                        sender_msgs, seal_lanes, pack_caches or {}
                    )
        except BaseException as err:
            flush.span.end(error=type(err).__name__)
            raise
        return flush

    def collect(self, flush: "_Launched") -> Tuple[np.ndarray, np.ndarray]:
        """The second half of :meth:`dispatch`: each launch's mask read back
        (``verify.device_wait``: the blocking read-back and nothing else),
        cut to its lanes, a kind's masks joined."""
        try:
            if flush.host is not None:
                out = flush.host
                self._note_served("host")
            else:
                out = tuple(self._read_back(flush.parts[kind]) for kind in _KINDS)
        except BaseException as err:
            flush.span.end(error=type(err).__name__)
            raise
        flush.span.end()
        metrics.observe(DISPATCH_MS_KEY, (time.perf_counter() - flush.t0) * 1e3)
        metrics.observe(DISPATCH_LANES_KEY, float(flush.total))
        return out

    # -- device route ----------------------------------------------------

    def _device(self, msgs, lanes, owners, flush: "_Launched") -> None:
        # A kind's lanes in chunks of the widest program boot loaded: never
        # at a shape that would compile here.
        cap = self._rungs[-1] * (self.dp if self.mesh is not None else 1)
        for kind, items in zip(_KINDS, (msgs, lanes)):
            for i in range(0, len(items), cap):
                self._launch(kind, list(items[i : i + cap]), owners, flush)

    def _launch(self, kind: str, items: list, owners, flush: "_Launched") -> None:
        """One kind's lanes (at most the widest rung of them) on their way:
        one pack, one table, one kernel launch, noted in ``flush`` for
        :meth:`collect` to read back.

        Claimed-address table: every live lane's claimed signer is a member
        by construction, so the kernel's (sig & member) mask reduces to
        signature validity; tenant membership stays on host where each
        chain's own set applies.  Where the lanes claim more distinct
        signers than the rows boot loaded their width with (a tenant
        registered after boot, a peer that serves seals under addresses of
        its own making), they are cut in two, each half launched at its own
        width: never a shape that would compile here."""
        senders = kind == "senders"
        signers = list(
            dict.fromkeys(
                (m.sender for m in items) if senders else (seal.signer for _h, seal in items)
            )
        )
        padded = self._pad_lanes(len(items))
        rows = self._rung_tables.get(padded, 0)
        if rows and len(signers) > rows:
            metrics.inc_counter(TABLE_CUTS_KEY)
            half = len(items) // 2
            self._launch(kind, items[:half], owners, flush)
            self._launch(kind, items[half:], owners, flush)
            return
        with trace.span("verify.pack", kind=kind, lanes=len(items)) as span:
            if senders:
                # The pack sequence (cache-hit reuse, oversize payloads
                # digested on host) is the single-tenant plane's own
                # implementation, shared, not forked, so a fix there can
                # never miss this route.  Lookups are pre-routed per
                # tenant; stores route back through the owners shim.
                zw, r, s, v, claimed, live = vbatch.pack_sender_digest_rows(
                    items,
                    cache=_RoutingPackCache(owners),
                    hits=[
                        (owners[id(m)].lookup(m) if id(m) in owners else None)
                        for m in items
                    ],
                    pad_lanes=padded,
                )
            else:
                zw, r, s, v, claimed, live = pack_seal_lanes(items, pad_lanes=padded)
            table = self._table(signers, live.shape[0])
            span.note(table_rows=table.shape[0])
        flush.parts[kind].append(
            (self._sig_mask(zw, r, s, v, claimed, table, live), len(items))
        )

    def _program(self) -> tuple:
        """What the device route launches: ``(kernel, its name in the cost
        ledger, its route)``, the sharded mask program over an attached mesh,
        the single-device recover ladder otherwise."""
        if self.mesh is not None:
            return self._mask_kernel, "mesh_verify_mask", "mesh"
        return RECOVER_KERNEL, "ecdsa_recover", "device"

    def _sig_mask(self, zw, r, s, v, claimed, table, live):
        """One signature-validity kernel launch, not read back: the sharded
        mask program over an attached mesh, the single-device recover ladder
        otherwise (identical argument layout: mesh_batch kept the sharded
        program a thin shell around the single-chip one)."""
        kernel, program, route = self._program()
        if self._rung_tables and self._rung_tables.get(live.shape[0]) != table.shape[0]:
            # Boot pinned the shapes and this is none of them: it compiles
            # (or loads) on the flush thread.  Counted where it would happen.
            metrics.inc_counter(UNPINNED_LAUNCHES_KEY)
        # The call's own wall here, the read-back's in ``_read_back``: the
        # two together are what the ledger had of a launch read back at once.
        with cost_ledger.dispatch_span(
            program,
            route=route,
            live_mask=live,
            kernels=((program, kernel),),
            site="sched/dispatch.py:_device",
        ):
            # The packers' rows as they are (``zw`` the digest program's
            # rows on the sender side): the compiled call stages them.
            with trace.span(
                "verify.dispatch",
                route=route,
                # the sharded program keeps the scan (ops/pallas_ladder.py)
                ladder=(
                    "scan" if self.mesh is not None else vbatch.ladder_of(live.shape[0])
                ),
                table_rows=table.shape[0],
            ):
                return kernel(zw, r, s, v, claimed, table, live)

    def _read_back(self, parts: list) -> np.ndarray:
        """One kind's launches read back: each mask on the host
        (``verify.device_wait``), cut to its lanes, all of them joined."""
        _kernel, program, route = self._program()
        masks = []
        for launched, lanes in parts:
            t0 = time.perf_counter()
            with trace.span("verify.device_wait", route=route):
                mask = np.asarray(launched)
            cost_ledger.add_wall_ms(program, route, (time.perf_counter() - t0) * 1e3)
            self._note_served(f"{route}/{mask.shape[0]}")
            masks.append(mask[:lanes])
        return np.concatenate(masks) if masks else np.zeros(0, dtype=bool)

    # -- host route ------------------------------------------------------

    def _host(self, msgs, lanes, owners) -> Tuple[np.ndarray, np.ndarray]:
        digests: List[bytes] = []
        sigs: List[bytes] = []
        claimed: List[bytes] = []
        if msgs:
            payloads = []
            for m in msgs:
                owner = owners.get(id(m))
                hit = owner.lookup(m) if owner is not None else None
                payloads.append(
                    hit.payload
                    if hit is not None
                    else m.encode(include_signature=False)
                )
            digests.extend(keccak256_many(payloads))
            sigs.extend(m.signature for m in msgs)
            claimed.extend(m.sender for m in msgs)
        for proposal_hash, seal in lanes:
            digests.append(proposal_hash)
            sigs.append(seal.signature)
            claimed.append(seal.signer)
        mask = self._host_sig_ok(digests, sigs, claimed)
        return mask[: len(msgs)], mask[len(msgs) :]

    @staticmethod
    def _host_sig_ok(
        digests: List[bytes], sigs: List[bytes], claimed: List[bytes]
    ) -> np.ndarray:
        if not digests:
            return np.zeros(0, dtype=bool)
        from .. import native

        if native.load() is not None:
            # One bulk GIL-releasing call; the claimed-set table makes the
            # native membership check vacuous (recovered == claimed[i]
            # implies membership), leaving exactly signature validity.
            return native.verify_batch_sequential(
                digests, sigs, claimed, list(dict.fromkeys(claimed))
            )
        out = np.zeros(len(digests), dtype=bool)
        for i, (digest, sig, addr) in enumerate(zip(digests, sigs, claimed)):
            r = int.from_bytes(sig[:32], "big")
            s = int.from_bytes(sig[32:64], "big")
            pub = host_ecdsa.recover(digest, r, s, sig[64])
            out[i] = (
                pub is not None and host_ecdsa.pubkey_to_address(*pub) == addr
            )
        return out
