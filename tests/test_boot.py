"""Boot layer (ISSUE 16): AOT store, warm-start, live reconfiguration.

Pins the tentpole contracts:

* the pinned-program registry and ``docs/compile_budget.json`` share one
  key space (the snapshot IS the manifest — they can never drift);
* AOT sidecars are fingerprint-gated: a stale (other-jax/backend/
  topology) sidecar never counts as cached;
* the AOT manifest round-trips and flags fingerprint staleness;
* the second boot pays ZERO cold compiles — proven in a SUBPROCESS pair
  against one fresh cache dir, reading each boot's own compile ledger;
* warm-start replays finalized WAL seals into the seal/sig verdict
  caches with the exact cache keys;
* ``TenantScheduler`` live reconfiguration: zero-downtime add/remove
  (drained removal, stale handles shed to the host oracle), mid-traffic
  dispatcher swaps, and per-tenant budgets surfaced in ``stats()``;
* ``scripts/boot_check.py`` passes a genuine cold->warm manifest pair
  and fails a no-speedup or fingerprint-mismatched one.
"""

import json
import os
import pathlib
import subprocess
import sys
import threading

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(
    0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts")
)

from go_ibft_tpu.boot import aot  # noqa: E402
from go_ibft_tpu.boot.registry import program_registry  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# registry / fingerprint / manifest
# ---------------------------------------------------------------------------


def test_family_of_strips_shape_suffixes_iteratively():
    assert aot.family_of("digest_words_8l") == "digest_words"
    assert aot.family_of("bls_g2_merge_tree_128v") == "bls_g2_merge_tree"
    assert aot.family_of("mesh_verify_mask_8l_dp4") == "mesh_verify_mask"
    assert aot.family_of("ecmul2_base") == "ecmul2_base"


def test_registry_keys_match_compile_budget_snapshot():
    with open(REPO / "docs" / "compile_budget.json") as fh:
        snapshot = json.load(fh)
    pinned = {k for k in snapshot if not k.startswith("_")}
    assert set(program_registry()) == pinned


def test_registry_selection_and_unknown_program():
    sub = program_registry(["digest_words_8l"])
    assert list(sub) == ["digest_words_8l"]
    with pytest.raises(KeyError):
        program_registry(["not_a_pinned_program"])


def test_fingerprint_carries_the_artifact_validity_key():
    fp = aot.fingerprint()
    assert set(fp) == {"jax", "backend", "device_count"}
    import jax

    assert fp["jax"] == jax.__version__


def test_manifest_roundtrip_and_staleness(tmp_path):
    path = str(tmp_path / "aot_manifest.json")
    doc = aot.write_manifest(
        path, {"digest_words": {"compile_ms": 430.5, "events": 1}}
    )
    assert doc["programs"]["digest_words"]["compile_ms"] == 430.5
    loaded = aot.load_manifest(path)
    assert loaded is not None and loaded["stale"] is False
    # A manifest minted under another jax/backend/topology is stale:
    # every family becomes a cold candidate again.
    doc["fingerprint"]["jax"] = "0.0.1"
    with open(path, "w") as fh:
        json.dump(doc, fh)
    assert aot.load_manifest(path)["stale"] is True
    assert aot.load_manifest(str(tmp_path / "missing.json")) is None


def test_stale_sidecar_never_counts_as_cached(tmp_path):
    store = aot.AOTStore(str(tmp_path))
    good = {
        "program": "digest_words_8l",
        "family": "digest_words",
        "fingerprint": aot.fingerprint(),
        "status": "cold",
        "compile_ms": 430.0,
    }
    store._write_sidecar("digest_words_8l", good)
    assert store.cached_programs() == {"digest_words_8l"}
    stale = dict(good, fingerprint={"jax": "0.0.1", "backend": "x", "device_count": 1})
    store._write_sidecar("digest_words_8l", stale)
    assert store.cached_programs() == set()
    # Unparseable sidecars degrade to "not cached", never a fault.
    with open(store._sidecar_path("digest_words_8l"), "w") as fh:
        fh.write("not json")
    assert store.cached_programs() == set()


# ---------------------------------------------------------------------------
# the second-boot proof (subprocess pair, one fresh cache dir)
# ---------------------------------------------------------------------------


def _boot_once(tag: str, cache_dir: str, tmp_path) -> tuple:
    """One full boot in a child process; returns (report, ledger events)."""
    ledger = tmp_path / f"compile_ledger_{tag}.jsonl"
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["GO_IBFT_COMPILE_LEDGER"] = str(ledger)
    # Persist even the sub-second digest compile (jax's floor is 1 s), so
    # that the first boot writes a cache entry and the second one hits it.
    env["GO_IBFT_CACHE_MIN_COMPILE_S"] = "0"
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "go_ibft_tpu.boot",
            "--programs",
            "digest_words_8l",
            "--no-chain",
        ],
        capture_output=True,
        text=True,
        env=env,
        cwd=str(REPO),
        timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    events = []
    if ledger.exists():
        events = [
            json.loads(ln) for ln in ledger.read_text().splitlines() if ln
        ]
    return report, events


def test_second_boot_pays_zero_cold_compiles(tmp_path):
    cache_dir = str(tmp_path / "xla")
    cold_report, cold_events = _boot_once("cold", cache_dir, tmp_path)
    # Empty cache directory: the first boot MUST pay and record.
    assert cold_report["cold"] >= 1
    assert cold_report["programs"]["digest_words_8l"]["status"] == "cold"
    assert len(cold_events) >= 1
    assert {e["program"] for e in cold_events} == {"digest_words"}

    warm_report, warm_events = _boot_once("warm", cache_dir, tmp_path)
    # Same cache dir: the second boot loads everything — zero cold
    # classifications AND zero compile-ledger events.
    assert warm_report["cold"] == 0
    assert warm_report["programs"]["digest_words_8l"]["status"] == "cached"
    assert warm_events == []


# ---------------------------------------------------------------------------
# warm-start verdict-cache seeding
# ---------------------------------------------------------------------------


class _Seal:
    def __init__(self, signer: bytes, signature: bytes) -> None:
        self.signer = signer
        self.signature = signature


class _Block:
    def __init__(self, height, proposal, seals, cert=None) -> None:
        self.height = height
        self.proposal = proposal
        self.seals = seals
        self.cert = cert


class _Handle:
    def __init__(self):
        self.entries = []

    def seed_seal_verdicts(self, entries) -> int:
        self.entries.extend(entries)
        return len(self.entries)


class _SigCache:
    def __init__(self):
        self.stored = {}

    def store_batch(self, keys, verdicts) -> None:
        self.stored.update(zip(keys, verdicts))


def test_seed_verdict_caches_replays_wal_seals_with_exact_keys():
    from go_ibft_tpu.boot.warmstart import seed_verdict_caches
    from go_ibft_tpu.crypto.backend import proposal_hash_of
    from go_ibft_tpu.messages.wire import Proposal

    prop = Proposal(raw_proposal=b"boot seed block", round=0)
    h = proposal_hash_of(prop)
    seal = _Seal(b"\x11" * 20, b"\x22" * 65)
    blocks = [
        _Block(1, prop, [seal]),
        _Block(2, prop, [], cert=object()),  # aggregate cert: no lanes
        _Block(3, prop, []),  # sealless: skipped
    ]
    handle, sig_cache = _Handle(), _SigCache()
    out = seed_verdict_caches(blocks, handle=handle, sig_cache=sig_cache)
    assert out == {"seal_verdicts": 1, "sig_verdicts": 1}
    ((key, verdict),) = handle.entries
    assert key == (seal.signer, h, seal.signature, 1)
    assert verdict is True
    assert sig_cache.stored == {(h, seal.signer, seal.signature): True}


def test_scheduler_handle_seed_seal_verdicts_prewarms_cache():
    from go_ibft_tpu.bench.workload import build_signed_round
    from go_ibft_tpu.crypto import PrivateKey
    from go_ibft_tpu.crypto.backend import ECDSABackend
    from go_ibft_tpu.sched import TenantScheduler

    r = build_signed_round(4, seed=41)
    keys = [PrivateKey.from_seed(b"bench-41-%d" % i) for i in range(4)]
    src = ECDSABackend.static_validators({k.address: 1 for k in keys})
    sched = TenantScheduler(window_s=0.001, route="host")
    handle = sched.register("warm", src)
    entries = [
        ((seal.signer, r.proposal_hash, seal.signature, 7), True)
        for seal in r.seals
    ]
    assert handle.seed_seal_verdicts(entries) == len(entries)
    stats = sched.stats()
    budgets = stats["tenants"]["warm"]["budgets"]
    assert budgets["verdict_entries"] == len(entries)


# ---------------------------------------------------------------------------
# live reconfiguration
# ---------------------------------------------------------------------------


def _signed_round_with_oracle(seed: int = 51, n: int = 4):
    from go_ibft_tpu.bench.workload import build_signed_round
    from go_ibft_tpu.crypto import PrivateKey
    from go_ibft_tpu.crypto.backend import ECDSABackend
    from go_ibft_tpu.verify import HostBatchVerifier

    r = build_signed_round(n, seed=seed, corrupt_frac=0.25)
    keys = [PrivateKey.from_seed(b"bench-%d-%d" % (seed, i)) for i in range(n)]
    src = ECDSABackend.static_validators({k.address: 1 for k in keys})
    oracle = HostBatchVerifier(src).verify_senders(r.prepares)
    return r, src, oracle


def test_add_remove_tenant_drains_and_stale_handle_sheds():
    from go_ibft_tpu.sched import TenantScheduler

    r, src, oracle = _signed_round_with_oracle()
    with TenantScheduler(window_s=0.001, route="host") as sched:
        handle = sched.add_tenant("ephemeral", src)
        assert (handle.verify_senders(r.prepares) == oracle).all()
        assert sched.remove_tenant("ephemeral", timeout_s=10.0) is True
        assert "ephemeral" not in sched.stats()["tenants"]
        # The stale handle still answers — shed to the host oracle, not
        # queued into a tenant nothing selects (and not a 30 s timeout).
        assert (handle.verify_senders(r.prepares) == oracle).all()
        assert (
            handle.verify_committed_seals(r.proposal_hash, r.seals, 1)
            == r.expected_seal_mask
        ).all()


def test_remove_tenant_without_drain_discards_queue():
    from go_ibft_tpu.sched import TenantScheduler

    r, src, _oracle = _signed_round_with_oracle()
    sched = TenantScheduler(window_s=60.0, route="host")  # never flushes
    sched.register("stuck", src)
    # Not running: nothing will drain; drain=False must not block.
    assert sched.remove_tenant("stuck", drain=False) in (True, False)
    assert "stuck" not in sched.stats()["tenants"]


def test_reconfigure_swaps_dispatcher_under_live_traffic():
    from go_ibft_tpu.sched import TenantScheduler

    r, src, oracle = _signed_round_with_oracle()
    with TenantScheduler(window_s=0.001, route="host") as sched:
        handle = sched.register("live", src)
        stop = threading.Event()
        failures = []

        def pound():
            while not stop.is_set():
                if not (handle.verify_senders(r.prepares) == oracle).all():
                    failures.append("verdict diverged")
                    return

        t = threading.Thread(target=pound)
        t.start()
        try:
            for dp in (2, None, 4):
                desc = sched.reconfigure(dp=dp)
                assert desc["new"]["route"] == "host"
                assert sched.stats()["dispatcher"] == desc["new"]
        finally:
            stop.set()
            t.join()
        assert not failures
        # Traffic submitted during the swaps all verified.
        assert (handle.verify_senders(r.prepares) == oracle).all()


def test_per_tenant_budgets_surface_in_stats():
    from go_ibft_tpu.sched import SchedQueueFull, TenantScheduler

    r, src, _oracle = _signed_round_with_oracle()
    sched = TenantScheduler(window_s=60.0, route="host", max_queue_lanes=4096)
    sched.register(
        "budgeted",
        src,
        max_queue_lanes=2,
        pack_cache_cap=3,
        verdict_cache_cap=5,
    )
    row = sched.stats()["tenants"]["budgeted"]
    assert row["draining"] is False
    assert row["budgets"] == {
        "queue_lanes_cap": 2,
        "pack_entries": 0,
        "pack_cap": 3,
        "verdict_entries": 0,
        "verdict_cap": 5,
    }
    # The per-tenant cap binds BEFORE the scheduler-wide one: 4 lanes
    # into a 2-lane budget (window too long to flush them first) must
    # refuse on THIS tenant's cap, not the 4096-lane scheduler default.
    import numpy as np

    tenant = sched._tenants["budgeted"]
    with sched:
        with pytest.raises(SchedQueueFull, match=r"cap 2"):
            sched.submit(
                tenant,
                "senders",
                list(range(4)),
                None,
                np.zeros(4, bool),
                [0, 1, 2, 3],
            )


# ---------------------------------------------------------------------------
# boot_check wiring
# ---------------------------------------------------------------------------


def test_boot_check_passes_speedup_and_fails_regression():
    import boot_check

    fp = {"jax": "0.4.37", "backend": "cpu", "device_count": 8}
    cold = {
        "fingerprint": fp,
        "programs": {"digest_words": {"compile_ms": 430.0, "events": 1}},
    }
    warm = {
        "fingerprint": fp,
        "programs": {"digest_words": {"compile_ms": 40.0, "events": 0}},
    }
    assert boot_check.check(cold, warm, ratio=0.5) == []
    # Second boot as slow as the first: the cache did not absorb it.
    slow = {
        "fingerprint": fp,
        "programs": {"digest_words": {"compile_ms": 430.0, "events": 1}},
    }
    assert boot_check.check(cold, slow, ratio=0.5)
    # Fingerprint mismatch: the runs keyed different caches.
    other = dict(warm, fingerprint=dict(fp, jax="0.0.1"))
    assert boot_check.check(cold, other, ratio=0.5)
    # A "cold" run that never compiled proves nothing.
    hollow = {
        "fingerprint": fp,
        "programs": {"digest_words": {"compile_ms": 0.0, "events": 0}},
    }
    assert boot_check.check(hollow, warm, ratio=0.5)
