#!/usr/bin/env python
"""Runtime cost report: top programs by wall time, waste, compile cost.

The read side of the ISSUE 14 cost ledger.  One source is required:

* ``--drain`` — run a small in-process host-route drain with the ledger
  enabled and report the live snapshot (the ``make cost-report`` CI
  smoke: proves the whole plane — seams, accumulators, attribution —
  renders end to end without any device compile);
* ``--snapshot FILE`` — an ``obs.ledger.snapshot()`` a process saved as
  JSON.

``--compile-ledger compile_ledger.jsonl`` — the append-only compile event
log (cold-compile duration table per program + call site) — is printed
alongside either when the file exists.

Attribution: dispatch records use the family names of the
``scripts/compile_budget.py`` registry (shape suffixes stripped), so the
report maps recorded dispatches onto the pinned program set and prints
the attributed fraction — ``--check`` fails below ``--min-attribution``
(default 0.95) and when a pinned family with recorded dispatches is
missing from the rendered table.

``make cost-report`` runs ``--drain --check``.
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Align with the boot plane's topology (tests/conftest.py, boot/__main__,
# scripts/warm_kernels.py): the device-count flag is part of both the
# persistent-cache key and the AOT sidecar fingerprint, so the `cached?`
# column must read the store under the same posture it was minted with.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

BUDGET_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs",
    "compile_budget.json",
)

# Shape suffixes the compile-budget registry appends to family names:
# lane/validator buckets (_8l, _128v, ...) and mesh extents (_dp2 ...).
_SHAPE_SUFFIX = re.compile(r"(_dp\d+|_\d+[lv])$")


def pinned_families(budget_path: str = BUDGET_PATH) -> set:
    """Compile-budget registry keys with shape suffixes stripped — THE
    program key space dispatch records attribute into."""
    try:
        with open(budget_path) as fh:
            snapshot = json.load(fh)
    except (OSError, ValueError):
        return set()
    families = set()
    for key in snapshot:
        if key.startswith("_"):
            continue
        family = key
        while True:
            stripped = _SHAPE_SUFFIX.sub("", family)
            if stripped == family:
                break
            family = stripped
        families.add(family)
    return families


def aot_cached_families() -> set:
    """Families the AOT store holds under THIS process's fingerprint.

    Sourced from the boot store's sidecars (``<cache_dir>/aot/``): a
    family in this set was compiled at BOOT (warm_kernels / boot layer)
    and the next boot loads it from cache; anything else in the compile
    tables was paid at first dispatch, mid-round — the exact cost the
    warm-start plane exists to remove."""
    try:
        from go_ibft_tpu.boot.aot import AOTStore, family_of

        return {family_of(p) for p in AOTStore().cached_programs()}
    except Exception:  # noqa: BLE001 - report must render without jax
        return set()


def _cached_tag(name: str, aot_families: set) -> str:
    return "boot" if name in aot_families else "first-dispatch"


def _table(headers, rows) -> str:
    rows = [tuple(str(c) for c in row) for row in rows]
    all_rows = [tuple(headers)] + rows
    widths = [max(len(r[i]) for r in all_rows) for i in range(len(headers))]
    out = []
    for i, row in enumerate(all_rows):
        out.append("  ".join(c.ljust(widths[j]) for j, c in enumerate(row)))
        if i == 0:
            out.append("-" * len(out[0]))
    return "\n".join(out)


def render_snapshot(snap: dict, *, top: int = 20, families=None) -> str:
    """The per-program report over one ledger snapshot."""
    families = pinned_families() if families is None else families
    rows = snap.get("dispatches", [])
    lines = []

    lines.append(f"== top {min(top, len(rows))} programs by wall time ==")
    table_rows = []
    for row in rows[:top]:
        waste = row["padded_lanes"] - row["live_lanes"]
        table_rows.append(
            (
                row["program"],
                row["route"],
                row["dispatches"],
                row["live_lanes"],
                row["padded_lanes"],
                "-" if row["occupancy"] is None else f"{row['occupancy']:.3f}",
                waste,
                f"{row['wall_ms']:.1f}",
                "yes" if row["program"] in families else "NO",
            )
        )
    lines.append(
        _table(
            (
                "program",
                "route",
                "dispatches",
                "live",
                "padded",
                "occupancy",
                "waste",
                "wall_ms",
                "pinned",
            ),
            table_rows,
        )
    )

    total = sum(r["dispatches"] for r in rows)
    attributed = sum(
        r["dispatches"] for r in rows if r["program"] in families
    )
    fraction = attributed / total if total else None
    lines.append("")
    lines.append(
        "attribution: "
        + (
            f"{attributed}/{total} dispatches "
            f"({fraction:.1%}) map to pinned compile-budget families"
            if total
            else "no dispatches recorded"
        )
    )
    unpinned = sorted(
        {r["program"] for r in rows if r["program"] not in families}
    )
    if unpinned:
        lines.append(f"unpinned programs: {', '.join(unpinned)}")
    if snap.get("overflowed"):
        lines.append(
            f"WARNING: {snap['overflowed']} records landed in the overflow "
            "bucket (program key space exceeded the ledger cap)"
        )

    compiles = snap.get("compiles", {})
    if compiles:
        aot_families = aot_cached_families()
        lines.append("")
        lines.append("== compile cost (per program) ==")
        lines.append(
            _table(
                ("program", "compiles", "compile_ms", "cached?"),
                [
                    (
                        name,
                        acc["count"],
                        f"{acc['ms']:.1f}",
                        _cached_tag(name, aot_families),
                    )
                    for name, acc in sorted(
                        compiles.items(), key=lambda kv: -kv[1]["ms"]
                    )
                ],
            )
        )
    return "\n".join(lines)


def render_compile_ledger(path: str, *, top: int = 30) -> str:
    """Cold-compile duration table from the append-only event log."""
    events = []
    try:
        with open(path) as fh:
            for raw in fh:
                raw = raw.strip()
                if not raw.startswith("{"):
                    continue
                try:
                    event = json.loads(raw)
                except ValueError:
                    continue
                if "program" in event and "ms" in event:
                    events.append(event)
    except OSError:
        return f"(no compile ledger at {path!r})"
    if not events:
        return f"(compile ledger {path!r} holds no events)"
    events.sort(key=lambda e: -e["ms"])
    aot_families = aot_cached_families()
    lines = [
        f"== compile events in {path} — append-only across runs "
        f"({len(events)} total, top {min(top, len(events))} by duration) =="
    ]
    lines.append(
        _table(
            ("program", "ms", "shared", "cached?", "site"),
            [
                (
                    e["program"],
                    f"{e['ms']:.1f}",
                    e.get("shared_span", 1),
                    _cached_tag(e["program"], aot_families),
                    e.get("site", ""),
                )
                for e in events[:top]
            ],
        )
    )
    return "\n".join(lines)


def run_drain(compile_log=None) -> dict:
    """A small host-route drain with the ledger on (the CI smoke).

    Exercises two pinned program families without a single XLA compile:
    the coalesced host recover flush (``ecdsa_recover``) and the batched
    host multi-pairing (``bls_multipair_miller``).  Returns the live
    snapshot.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from go_ibft_tpu.crypto import PrivateKey
    from go_ibft_tpu.crypto import bls as hbls
    from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
    from go_ibft_tpu.messages.helpers import extract_committed_seal
    from go_ibft_tpu.messages.wire import Proposal, View
    from go_ibft_tpu.obs import ledger as cost_ledger
    from go_ibft_tpu.sched import CoalescedDispatcher
    from go_ibft_tpu.verify.aggregate import multi_aggregate_check

    cost_ledger.enable(compile_log=compile_log)

    # Coalesced host recover flush over real seals.
    keys = [PrivateKey.from_seed(b"cost-report-%d" % i) for i in range(4)]
    src = ECDSABackend.static_validators({k.address: 1 for k in keys})
    backends = [ECDSABackend(k, src) for k in keys]
    view = View(height=1, round=0)
    phash = proposal_hash_of(Proposal(raw_proposal=b"cost report drain", round=0))
    seals = [
        extract_committed_seal(b.build_commit_message(phash, view))
        for b in backends
    ]
    sender_ok, seal_ok = CoalescedDispatcher(route="host").dispatch(
        [], [(phash, seal) for seal in seals]
    )
    assert seal_ok.all(), "drain verdicts wrong — refusing to report"

    # Batched host multi-pairing over a real aggregate lane.
    blk = [hbls.BLSPrivateKey.from_seed(b"cost-report-%d" % i) for i in range(2)]
    lanes = [
        (phash, [k.sign(phash) for k in blk], [k.pubkey for k in blk])
    ] * 2
    assert multi_aggregate_check(lanes, route="host").all()
    return cost_ledger.snapshot()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    source_arg = parser.add_mutually_exclusive_group(required=True)
    source_arg.add_argument(
        "--snapshot", help="a saved cost-ledger snapshot (JSON) to report over"
    )
    source_arg.add_argument(
        "--drain",
        action="store_true",
        help="run a small in-process host drain and report its ledger",
    )
    parser.add_argument("--compile-ledger", default="compile_ledger.jsonl")
    parser.add_argument("--top", type=int, default=20)
    parser.add_argument(
        "--check",
        action="store_true",
        help="CI smoke: fail unless the report renders, every pinned "
        "family with recorded dispatches appears, and attribution "
        "meets --min-attribution",
    )
    parser.add_argument("--min-attribution", type=float, default=0.95)
    args = parser.parse_args()

    if args.drain:
        snap = run_drain(compile_log=args.compile_ledger)
        source = "in-process drain"
    else:
        try:
            with open(args.snapshot) as fh:
                snap = json.load(fh)
        except (OSError, ValueError) as err:
            print(
                f"cost_report: cannot read snapshot {args.snapshot!r} ({err})",
                file=sys.stderr,
            )
            return 2
        source = args.snapshot

    families = pinned_families()
    # --check asserts every pinned family that ran APPEARS in the
    # rendered table — so check mode never truncates (a healthy run with
    # many (program, route) rows must not fail on table length alone).
    if args.check:
        args.top = max(args.top, len(snap.get("dispatches", [])))
    report = render_snapshot(snap, top=args.top, families=families)
    print(f"cost report — source: {source}")
    print(report)
    if os.path.exists(args.compile_ledger):
        print()
        print(render_compile_ledger(args.compile_ledger))

    if args.check:
        rows = snap.get("dispatches", [])
        total = sum(r["dispatches"] for r in rows)
        if total == 0:
            print("cost_report --check: FAIL (no dispatches recorded)")
            return 1
        ran = {r["program"] for r in rows if r["program"] in families}
        rendered = {r["program"] for r in rows[: args.top]}
        missing = [f for f in sorted(ran) if f not in rendered]
        attributed = sum(
            r["dispatches"] for r in rows if r["program"] in families
        )
        fraction = attributed / total
        failures = []
        if missing:
            failures.append(f"pinned families missing from report: {missing}")
        if fraction < args.min_attribution:
            failures.append(
                f"attribution {fraction:.1%} < {args.min_attribution:.0%}"
            )
        if failures:
            print("cost_report --check: FAIL")
            for failure in failures:
                print(f"  - {failure}")
            return 1
        print(
            f"cost_report --check: ok ({len(ran)} pinned families, "
            f"attribution {fraction:.1%})"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
