"""Pre-warm the expensive kernel compiles into the persistent XLA cache.

CI's slow tier runs each test under a per-test timeout; a cold-cache BLS
pairing or EC-ladder compile can exceed that budget on a weak host.  This
script runs compiles with NO per-test timeout so the subsequent pytest run
only pays cache loads.  Shapes warmed here are the ones the slow suites
actually dispatch (verifier buckets + workload buckets + the pairing
program + the Pallas interpret-mode keccak).

The ``XLA_FLAGS`` device-count flag is part of the persistent-cache key,
so this script force-matches tests/conftest.py's 8-virtual-device setup
BEFORE jax loads — warmed programs must be loadable by the test suite.

The pinned compile-budget families warm FIRST through the AOT program
store (``go_ibft_tpu/boot/aot.py``): families whose store sidecar says a
prior run already compiled them into this cache under the same
jax/backend/topology fingerprint are SKIPPED (``--no-skip`` forces a full
re-warm), so a second warm run costs seconds, not minutes.  The runtime
warm steps below it re-warm the extra non-pinned shapes (big buckets,
Pallas, multi-pairing lanes) every run — on a warm cache those are cache
loads, which is exactly the cheap path.

Usage: ``python scripts/warm_kernels.py [--skip-bls] [--skip-mesh]
[--skip-aot] [--aot-only] [--no-skip] [--programs a,b] [--assert-warm]
[--manifest out.json]``

* ``--manifest out.json`` — write the machine-readable AOT manifest
  (fingerprint + per-family measured compile cost) that
  ``python -m go_ibft_tpu.boot --manifest`` / ``warm_start(manifest=)``
  consume to select their restore set;
* ``--aot-only [--programs k1,k2]`` — restore just the (selected) pinned
  families through the AOT store and exit: the fast CI boot check;
* ``--assert-warm`` — exit non-zero if the AOT restore classified ANY
  program as a cold compile: run twice against the same cache dir and
  the second run proves the cache (the CI ``boot-check`` gate).
"""

import os
import sys
import time

sys.path.insert(0, ".")

# Must match tests/conftest.py (same flag => same persistent-cache key).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

def _argval(flag: str) -> str:
    for i, arg in enumerate(sys.argv):
        if arg == flag and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return ""


def _stamp(label: str, t0: float, program: str = None) -> None:
    """Print the step duration; with ``program`` set, ALSO record it to
    the compile ledger — used ONLY for steps whose kernels this script
    cannot introspect (the Pallas kernel).  Every other
    step's true compiles are recorded by the seam-level cache
    introspection inside the warmup()/entry-point it drives, so stamping
    those here would double-count each cold build (and record cache
    LOADS as compiles on a warm persistent cache)."""
    from go_ibft_tpu.obs import ledger as cost_ledger

    elapsed_s = time.perf_counter() - t0
    print(f"[warm] {label}: {elapsed_s:.1f}s", flush=True)
    if program is not None:
        cost_ledger.record_compile(
            program,
            elapsed_s * 1e3,
            site="scripts/warm_kernels.py (step duration, not introspected)",
        )


def _warm_aot_store() -> int:
    """Restore the pinned compile-budget families through the AOT store,
    skipping families a prior run already compiled into this cache (their
    sidecar fingerprint matches this process).  Returns the number of
    programs classified as COLD compiles (``--assert-warm`` evidence)."""
    from go_ibft_tpu.boot.aot import AOTStore

    store = AOTStore(site="scripts/warm_kernels.py (aot)")
    requested = _argval("--programs")
    programs = (
        [s for s in requested.split(",") if s]
        if requested
        else list(store.pinned_programs())
    )
    skipped = []
    if "--no-skip" not in sys.argv:
        cached = store.cached_programs()
        skipped = [p for p in programs if p in cached]
        programs = [p for p in programs if p not in cached]
    if skipped:
        print(
            f"[warm] aot: {len(skipped)} program(s) already cached "
            f"(sidecar fingerprint match) — skipped: {','.join(skipped)}",
            flush=True,
        )
    cold = 0
    if programs:
        t0 = time.perf_counter()
        statuses = store.ensure(programs)
        for name, st in statuses.items():
            print(
                f"[warm] aot: {name}: {st.status} "
                f"(compile {st.compile_ms / 1e3:.1f}s, "
                f"lower {st.lower_ms / 1e3:.1f}s)"
                + (f" — {st.reason}" if st.reason else ""),
                flush=True,
            )
        cold = sum(1 for st in statuses.values() if st.status == "cold")
        _stamp(f"AOT program store ({len(programs)} program(s))", t0)
    return cold


def _finish(cold: int) -> int:
    """The measured compile table + optional manifest, shared by the full
    warm flow and ``--aot-only``; returns the process exit code."""
    from go_ibft_tpu.obs import ledger as cost_ledger

    # The measured cold-compile (or cache-load) duration table, also
    # appended per event to compile_ledger.jsonl above — CI's archived
    # baseline for the ROADMAP-item-5 AOT compile cache.
    snap = cost_ledger.snapshot()
    if snap is not None and snap["compiles"]:
        print("[warm] compile ledger (per program):", flush=True)
        for name, acc in sorted(
            snap["compiles"].items(), key=lambda kv: -kv[1]["ms"]
        ):
            print(
                f"[warm]   {name}: {acc['count']} event(s), "
                f"{acc['ms'] / 1e3:.1f}s total",
                flush=True,
            )

    manifest_path = _argval("--manifest")
    if manifest_path:
        from go_ibft_tpu.boot.aot import AOTStore, family_of, write_manifest

        # Family-keyed measured costs: the store sidecars (authoritative
        # for the pinned set — they survive skip runs where the ledger
        # records nothing) overlaid with this run's ledger families (the
        # non-pinned extras the runtime steps compiled).
        store = AOTStore()
        programs: dict = {}
        for program in store.cached_programs():
            side = store.read_sidecar(program) or {}
            fam = programs.setdefault(
                family_of(program), {"compile_ms": 0.0, "events": 0}
            )
            fam["compile_ms"] += float(side.get("compile_ms", 0.0))
            fam["events"] += 1
        if snap is not None:
            for name, acc in snap["compiles"].items():
                fam = programs.setdefault(
                    name, {"compile_ms": 0.0, "events": 0}
                )
                fam["compile_ms"] += acc["ms"]
                fam["events"] += acc["count"]
        write_manifest(manifest_path, programs)
        print(
            f"[warm] aot manifest: {manifest_path} "
            f"({len(programs)} families)",
            flush=True,
        )

    cost_ledger.disable()
    if cold and "--assert-warm" in sys.argv:
        print(
            f"[warm] FAIL --assert-warm: {cold} cold compile(s) on a cache "
            "that was supposed to be warm",
            flush=True,
        )
        return 2
    return 0


def main() -> int:
    if "--help" in sys.argv or "-h" in sys.argv:
        print(__doc__)
        return 0

    from go_ibft_tpu.obs import ledger as cost_ledger
    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache

    enable_persistent_cache()
    cost_ledger.enable(
        compile_log=os.environ.get(
            "GO_IBFT_COMPILE_LEDGER", "compile_ledger.jsonl"
        )
    )

    # Pinned families first, through the AOT store: everything below then
    # loads from the persistent cache instead of compiling cold.
    cold = 0
    if "--skip-aot" not in sys.argv:
        cold = _warm_aot_store()
    if "--aot-only" in sys.argv:
        return _finish(cold)

    import jax.numpy as jnp

    from go_ibft_tpu.verify import DeviceBatchVerifier
    from go_ibft_tpu.verify.batch import committee_lanes

    # Mesh FIRST: the shard_map program is what a cold cache costs most;
    # everything after this line is cheaper to lose to a time limit.
    if "--skip-mesh" not in sys.argv:
        # MeshBatchVerifier's sharded mask program at the tier-1 test
        # shapes (dp=2 and dp=8, 8 local lanes, 8-row table): the oracle-
        # parity suite dispatches exactly these (and the dp=8 one is what
        # ``__graft_entry__.dryrun_multichip(8)`` runs), and a cold
        # shard_map compile inside a test timeout is the failure mode this
        # script exists to prevent.
        import jax

        from go_ibft_tpu.parallel import mesh_context
        from go_ibft_tpu.verify import MeshBatchVerifier

        from go_ibft_tpu.boot.aot import AOTStore as _AOTStore

        _mask_cached = (
            set() if "--no-skip" in sys.argv else _AOTStore().cached_programs()
        )
        for dp in (2, 8):
            if f"mesh_verify_mask_8l_dp{dp}" in _mask_cached:
                # Exact pin match: the AOT store already restored this
                # shard_map program into this cache — skip the lowering.
                print(f"[warm] mask program (dp={dp}): cached, skipped", flush=True)
                continue
            t0 = time.perf_counter()
            mv = MeshBatchVerifier(
                lambda h: {}, mesh=mesh_context(dp, devices=jax.devices()[:dp])
            )
            if mv.sharded:
                mv.warmup()
                _stamp(f"MeshBatchVerifier mask program (dp={dp})", t0)

    t0 = time.perf_counter()
    DeviceBatchVerifier(lambda h: {}).warmup()
    _stamp("DeviceBatchVerifier buckets", t0)

    # Early-exit drain shapes (ISSUE 9): the power-ordered chunked seal
    # drain dispatches the recover kernel at the quorum-prefix chunk
    # bucket — (128 lanes, 128-row table) for a 100-validator committee,
    # and the (8, 128) shape the weighted-committee suites hit.  Cold-
    # compiling either inside a test timeout is the failure mode this
    # script exists to prevent.
    # Since ISSUE 32 that committee's sender floods run at twice the rung
    # (256 lanes, each COMMIT's seal beside its envelope).
    t0 = time.perf_counter()
    DeviceBatchVerifier(lambda h: {}).warmup(
        lanes=(8, *committee_lanes(100)), table_rows=128
    )
    _stamp("early-exit and flood shapes (8/128/256 lanes x 128-row table)", t0)

    # Serve-path drain shapes (ISSUE 10): the proof-serving read plane's
    # device route is the multi-tenant CoalescedDispatcher — fresh proof
    # lanes coalesce into the SAME pinned recover/digest programs at the
    # claimed-signer-table shapes ((8, 8) for the tier-1 suites, (128,
    # 128) for a 100-validator quorum drain).  Cold-compiling either
    # inside a test timeout is the failure mode warmed here.
    t0 = time.perf_counter()
    from go_ibft_tpu.sched import CoalescedDispatcher

    CoalescedDispatcher(route="device").warmup(lanes=(8, 128), table_rows=128)
    _stamp("serve/sched coalesced drain shapes (8/128 lanes)", t0)

    # Lock-step cluster tick collective (ISSUE 17): the rows variant at
    # the 8-node real-crypto shape is AOT-pinned above (ici_tick_8n);
    # this additionally warms the lite variant at the 100-validator
    # soak shape (100 nodes -> 5-way shard over the 8 forced host
    # devices) so the tier-1 cluster soak never pays the gather compile
    # inside a per-test budget.
    t0 = time.perf_counter()
    import jax
    import numpy as _np_ici
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from go_ibft_tpu.net.ici import build_tick_program, shard_count

    _devs = jax.devices("cpu")
    _d = shard_count(100, len(_devs))
    if _d > 1:
        _mesh = Mesh(_np_ici.asarray(_devs[:_d]), ("node",))
        _prog = build_tick_program(_mesh)
        with cost_ledger.compile_watch(
            (("ici_tick", _prog),), site="scripts/warm_kernels.py"
        ):
            _staging = jax.device_put(
                jnp.zeros((100, 8, 1024), jnp.uint8),
                NamedSharding(_mesh, PartitionSpec("node")),
            )
            _prog(_staging).block_until_ready()
        _stamp("ici lock-step tick (100-node lite gather)", t0)

    t0 = time.perf_counter()
    from go_ibft_tpu.ops.pallas_keccak import keccak_f_pallas, pallas_supported

    state = jnp.zeros((1, 25, 2), dtype=jnp.uint32)
    keccak_f_pallas(state, interpret=not pallas_supported()).block_until_ready()
    _stamp("pallas keccak_f (50x128 tile)", t0, "pallas_keccak_f")

    if "--skip-bls" not in sys.argv:
        t0 = time.perf_counter()
        from go_ibft_tpu.bench.bls_workload import build_bls_round_workload
        from go_ibft_tpu.ops.bls12_381 import aggregate_verify_commit

        w = build_bls_round_workload(4, time_host=False)
        aggregate_verify_commit(*w.args).block_until_ready()
        _stamp("BLS pairing program (4v bucket)", t0)
        w = build_bls_round_workload(100, time_host=False)
        aggregate_verify_commit(*w.args).block_until_ready()
        _stamp("BLS pairing program (100v bucket)", t0)

        # ISSUE 12: the device-resident aggregation shapes.  The merge
        # trees are SMALL programs (one scanned point-add body) at the
        # tier-1 test bucket (8) and the mega-committee bucket (128);
        # the batched multi-pairing warms at the tiny 2-lane bucket the
        # slow-tier parity test dispatches — its Miller stage is the
        # big compile, and the final-exp stages are the SAME jit
        # objects warmed by the pairing programs above (batched shapes
        # still recompile per lane count, which is exactly what this
        # warms).
        import numpy as _np

        from go_ibft_tpu.crypto import bls as _hbls
        from go_ibft_tpu.ops.bls12_381 import (
            g1_merge_tree,
            g2_merge_tree,
            pack_g1_points,
            pack_g2_points,
        )

        merge_watch = (
            ("bls_g2_merge_tree", g2_merge_tree),
            ("bls_g1_merge_tree", g1_merge_tree),
        )
        for bucket in (8, 128):
            t0 = time.perf_counter()
            pts = [_hbls.g2_mul(3 + i, _hbls.G2_GEN) for i in range(2)]
            x0, x1, y0, y1 = pack_g2_points(pts + [None] * (bucket - 2))
            live = _np.zeros(bucket, dtype=bool)
            live[:2] = True
            with cost_ledger.compile_watch(
                merge_watch, site="scripts/warm_kernels.py"
            ):
                jnp.asarray(
                    g2_merge_tree(
                        jnp.asarray(x0),
                        jnp.asarray(x1),
                        jnp.asarray(y0),
                        jnp.asarray(y1),
                        jnp.asarray(live),
                    )[0]
                ).block_until_ready()
                if bucket == 128:
                    g1 = [_hbls.g1_mul(3 + i, _hbls.G1_GEN) for i in range(2)]
                    px, py = pack_g1_points(g1 + [None] * (bucket - 2))
                    jnp.asarray(
                        g1_merge_tree(
                            jnp.asarray(px), jnp.asarray(py), jnp.asarray(live)
                        )[0]
                    ).block_until_ready()
            _stamp(f"g2/g1 merge-tree kernels ({bucket} bucket)", t0)

        t0 = time.perf_counter()
        from go_ibft_tpu.verify.aggregate import multi_aggregate_check

        wkeys = [_hbls.BLSPrivateKey.from_seed(b"warm-mp-%d" % i) for i in range(2)]
        wmsg = b"warm multipair lane" + b"\x00" * 13
        lanes = [
            (
                wmsg,
                [_hbls.aggregate_signatures([k.sign(wmsg) for k in wkeys])],
                [k.pubkey for k in wkeys],
            )
        ] * 2
        assert multi_aggregate_check(lanes, route="device").all()
        _stamp("batched multi-pairing (2-lane bucket)", t0)

        # Checkpoint skip-chain verify shape (ISSUE 20): a cold sync
        # verifies ~log2(epochs) certificate lanes in ONE dispatch —
        # warm the 4-lane bucket so the first CheckpointClient on a
        # device route never compiles mid-sync.
        t0 = time.perf_counter()
        ck_lanes = [
            (
                b"warm ckpt lane %03d" % i + b"\x00" * 13,
                [_hbls.aggregate_signatures(
                    [k.sign(b"warm ckpt lane %03d" % i + b"\x00" * 13)
                     for k in wkeys]
                )],
                [k.pubkey for k in wkeys],
            )
            for i in range(4)
        ]
        assert multi_aggregate_check(ck_lanes, route="device").all()
        _stamp("checkpoint skip-chain multi-pairing (4-lane bucket)", t0)

    return _finish(cold)


if __name__ == "__main__":
    sys.exit(main())
