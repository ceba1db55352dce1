"""Compile-budget regression guard: stablehlo line counts of the hot programs.

Trace size IS compile time on XLA:CPU: the r04->r05
rounds cut the 8-lane ladder program's cold compile 265s -> 55s almost
entirely by shrinking the traced program (mul 811 -> 316 lines,
shear-reshape conv), and r06 cut it again (~-31%) by deduplicating
point-op instantiations.  Since PR 25 the conv keeps its limb axes
leading and sums the product's la sheared windows as la slices (the
batch-leading r05 form made the TPU transpose every padded product, and
a slice of the merged product is a copy unless it fuses into an add:
PERF.md section 6).  That is 2*la + 10 lines a conv, not 7: +13.6% on
ecdsa_recover (30,066 -> 34,147 on jax 0.9.0), about 1.5x on the BLS
programs (30 limbs, three convs a product), XLA:CPU compile of a ladder
program unchanged within noise.  PR 28 split ``point_add`` into a shared
incomplete core and the ``P == Q`` overlay (the ladder's scan body calls
the core): +83 lines on every ladder program (34,147 -> 34,230), one more
zero test in the overlay; what the ladder stopped executing was never a
second instantiation.  PR 30 folds the ladder's batch to full TPU tiles
only where lanes % 256 == 0 (+35 lines at 2,048 lanes); every program
pinned here is 8 lanes and did not move by a line.  PR 46 derives
``fields``' carry passes from tracked limb bounds (a ``mul`` 19 -> 5
passes mod P, 6 mod N): every ladder program shrank by a third
(ecdsa_recover 34,230 -> 21,669) and the snapshot was re-baselined so that
the ratchet holds the new size.  Those wins regress silently — one refactor
that unrolls a scan or forks a new shape instantiation quietly re-adds
minutes of cold compile.  This script LOWERS (never compiles — it stays fast on
any host) the programs that dominate the cold budget, counts their
stablehlo lines, and fails when any grows >10% over the checked-in
snapshot (docs/compile_budget.json).

Usage:
    python scripts/compile_budget.py            # compare vs snapshot (10%)
    python scripts/compile_budget.py --check    # CI ratchet: fail on >2%
    python scripts/compile_budget.py --write    # regenerate the snapshot

``--check`` is the CI gate (ISSUE 9 satellite): the default 10% slack
exists for local iteration, but a program that quietly grows 9% per PR
compounds into minutes of cold compile within a quarter — the ratchet
holds every pinned program within 2% of its snapshot, so growth must be
CONSCIOUS (shrink the program or re-baseline with --write in the same
PR, where review sees the new number).
"""

import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The shard_map mesh entries need virtual devices before the backend
# initializes (same trick as tests/conftest.py).  8 covers the per-dp
# budget sweep (dp = 2/4/8): the sharded program must stay a THIN SHELL
# around the single-chip one at EVERY dp — SPMD propagation or a
# collective regression that re-traces the EC ladder per shard shows up
# as per-dp line growth here first.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

SNAPSHOT = pathlib.Path(__file__).resolve().parent.parent / "docs" / "compile_budget.json"
# Local-iteration slack vs the CI ratchet (--check): trace size is
# cold-compile time, and sub-10% growths compound silently PR over PR.
GROWTH_LIMIT = 0.10
CHECK_GROWTH_LIMIT = 0.02


def _programs() -> dict:
    """Lower each budget-tracked program at its engine-hot shape.

    The shapes live in ``go_ibft_tpu/boot/registry.py`` (ISSUE 16: the
    AOT program store restores the SAME registry at boot, so the budget
    ratchet and the warm-start plane can never pin different programs).
    The why of each pin, kept from the original in-line registry:

    * The multi-chip program (shard_map meshes at dp = 2/4/8) pins one
      family per dp — ``mesh_verify_mask`` (the MeshBatchVerifier drain
      program at 8 LOCAL lanes per shard, so the per-dp delta isolates
      the shard_map wrapper).  It must stay a thin shell around the
      single-chip program — SPMD propagation or a collective regression
      that re-traces the EC ladder per shard shows up as per-dp growth
      first.
    * ``bls_aggregate_verify_8v`` (ISSUE 7): the largest trace in the
      repo (~414k stablehlo lines at 8 lanes on jax 0.4.37), the most
      cold-compile-sensitive — a tower-arithmetic refactor that
      re-instantiates the Fp12 ops per call site adds MINUTES of compile.
    * The ISSUE 12 aggregation families — the scanned g2 merge tree at
      the 128-validator bucket (ONE lax.scan over halving levels: bucket
      growth must NOT grow the trace proportionally) and the batched
      multi-pairing Miller stage at 8 lanes.  The final-exp stages are
      deliberately NOT pinned: multi_pairing_check reuses the SAME
      staged jit objects aggregate_verify_commit compiled (identity
      pinned by tests/test_aggregate.py).
    * The ISSUE 14 additions — the keccak digest pack and the G1 merge
      tree — exist so every family the cost ledger attributes has a pin.
    """
    out = {}
    from go_ibft_tpu.boot.registry import program_registry

    for name, build in program_registry().items():
        fn, args = build()
        out[name] = len(fn.lower(*args).as_text().splitlines())
    return out


def _check_sched_reuses_pinned_programs() -> list:
    """ISSUE 8 satellite: the multi-tenant coalesced dispatch
    (go_ibft_tpu/sched/dispatch.py) must run the EXISTING pinned jit
    programs — the very objects verify/batch.py compiled — so process-
    wide coalescing adds NO new program family to this budget (the
    snapshot gains no sched entries by construction).  A refactor that
    re-jits a private copy forks a second compile of the largest
    recover ladder; assert object identity so that fails CI here."""
    from go_ibft_tpu.sched import dispatch as sched_dispatch
    from go_ibft_tpu.verify import batch as vbatch

    failures = []
    if sched_dispatch.DIGEST_KERNEL is not vbatch._digest_kernel:
        failures.append(
            "sched.dispatch.DIGEST_KERNEL is not verify.batch._digest_kernel "
            "— the coalesced plane forked a second digest program"
        )
    if sched_dispatch.RECOVER_KERNEL is not vbatch._recover_kernel:
        failures.append(
            "sched.dispatch.RECOVER_KERNEL is not verify.batch._recover_kernel "
            "— the coalesced plane forked a second recover program"
        )
    print(
        json.dumps(
            {
                "check": "sched_reuses_pinned_programs",
                "status": "FAIL" if failures else "ok",
            }
        )
    )
    return failures


def main() -> int:
    import jax

    growth_limit = (
        CHECK_GROWTH_LIMIT if "--check" in sys.argv else GROWTH_LIMIT
    )
    t0 = time.time()
    measured = _programs()
    measured["_trace_seconds"] = round(time.time() - t0, 1)
    measured["_jax_version"] = jax.__version__

    if "--write" in sys.argv:
        SNAPSHOT.write_text(json.dumps(measured, indent=1) + "\n")
        print(json.dumps({"compile_budget": "snapshot written", **measured}))
        return 0

    identity_failures = _check_sched_reuses_pinned_programs()
    if identity_failures:
        print(
            json.dumps({"compile_budget": "FAIL", "failures": identity_failures})
        )
        return 1

    snapshot = json.loads(SNAPSHOT.read_text())
    if snapshot.get("_jax_version") != jax.__version__:
        # Lowering output is jax-version-sensitive: comparing line counts
        # across versions yields false positives (blocked PRs on an
        # unchanged repo) or false negatives (masked growth).  CI pins the
        # snapshot's jax; a deliberate bump re-baselines with --write.
        print(
            json.dumps(
                {
                    "compile_budget": "FAIL",
                    "failures": [
                        f"snapshot from jax {snapshot.get('_jax_version')} but "
                        f"running jax {jax.__version__}: line counts are not "
                        "comparable across lowering versions — pin jax or "
                        "re-baseline with --write"
                    ],
                }
            )
        )
        return 1
    failures = []
    for name, lines in measured.items():
        if name.startswith("_"):
            continue
        base = snapshot.get(name)
        if base is None:
            failures.append(f"{name}: no snapshot entry (run --write)")
            continue
        growth = (lines - base) / base
        status = "FAIL" if growth > growth_limit else "ok"
        print(
            json.dumps(
                {
                    "program": name,
                    "lines": lines,
                    "snapshot": base,
                    "growth": round(growth, 4),
                    "limit": growth_limit,
                    "status": status,
                }
            )
        )
        if growth > growth_limit:
            failures.append(
                f"{name}: {lines} lines vs snapshot {base} (+{growth:.1%} > "
                f"{growth_limit:.0%}) — trace size is cold-compile time; "
                "shrink the program or consciously re-baseline with --write"
            )
    if failures:
        print(json.dumps({"compile_budget": "FAIL", "failures": failures}))
        return 1
    print(json.dumps({"compile_budget": "ok", "trace_seconds": measured["_trace_seconds"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
