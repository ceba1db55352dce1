"""SLO gates: soak records graded pass / warn / fail against absolute limits.

Soak SLOs are absolute contracts — liveness either held or it did not,
regardless of history.  The soaks (chain soak, chaos matrix, cluster and
Byzantine sims, the fleet and timeline smokes) emit one JSONL record per
SLO::

    {"slo": "missed_heights", "value": 0, "unit": "heights",
     "warn": 0, "fail": 0, "context": {"nodes": 30, "heights": 20}}

and :func:`gate_slo_records` grades each record direction-aware against its
limits (per-record limits win; :data:`DEFAULT_SLO_TABLE` supplies the
standing ones).  ``value > fail`` (or ``< fail`` for higher-is-better SLOs)
fails the run; ``scripts/slo_gates.py`` is the CLI.  Speed is not gated
here: the benchmark's numbers are the driver's ``PERF_LEDGER.jsonl``.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional

__all__ = [
    "GateResult",
    "render_table",
    "SloSpec",
    "DEFAULT_SLO_TABLE",
    "slo_record",
    "append_slo_records",
    "parse_slo_records",
    "gate_slo_records",
]


@dataclass
class GateResult:
    config: str
    backend: str
    status: str  # "pass" | "warn" | "fail" | "info"
    fresh: Optional[float]
    prior: Optional[float]
    prior_source: str
    change_pct: Optional[float]  # signed; positive = regression
    note: str = ""


def render_table(results: List[GateResult]) -> str:
    """Fixed-width pass/warn/fail table for terminals and CI logs."""
    headers = ("config", "backend", "fresh", "best prior", "source", "Δ%", "status")
    rows = [headers]
    for r in results:
        rows.append(
            (
                r.config,
                r.backend,
                "-" if r.fresh is None else f"{r.fresh:g}",
                "-" if r.prior is None else f"{r.prior:g}",
                r.prior_source,
                "-" if r.change_pct is None else f"{r.change_pct:+.1f}",
                r.status.upper() + (f"  ({r.note})" if r.note else ""),
            )
        )
    widths = [
        max(len(row[i]) for row in rows) for i in range(len(headers) - 1)
    ]
    out = []
    for i, row in enumerate(rows):
        line = "  ".join(
            cell.ljust(widths[j]) for j, cell in enumerate(row[:-1])
        )
        out.append(line + "  " + row[-1])
        if i == 0:
            out.append("-" * len(out[0]))
    return "\n".join(out)


@dataclass
class SloSpec:
    """Standing limits for one SLO family."""

    warn: Optional[float]
    fail: Optional[float]
    higher_is_better: bool = False
    unit: str = ""


DEFAULT_SLO_TABLE: Dict[str, SloSpec] = {
    # Liveness: ANY missed height is a failure, in every soak.
    "missed_heights": SloSpec(warn=0, fail=0, unit="heights"),
    # Safety proxy: divergent per-node chains (should be impossible; the
    # soaks also assert it directly, but the record makes CI evidence).
    "diverged_chains": SloSpec(warn=0, fail=0, unit="nodes"),
    # Latency: per-height finalize tail under chaos.  The standing limits
    # are deliberately loose (CI hosts vary wildly); individual soaks
    # pass tighter per-record limits scaled to their round timeout.
    "finalize_p99_ms": SloSpec(warn=10_000.0, fail=30_000.0, unit="ms"),
    # Degradation budgets: shed verify work and quarantined lanes are
    # legitimate under injected faults but a sudden flood of either is a
    # regression in disguise.
    "shed_lanes": SloSpec(warn=0, fail=None, unit="lanes"),
    "quarantined_lanes": SloSpec(warn=0, fail=None, unit="lanes"),
    # Sync should only ever repair stranded tails, never carry the soak.
    "sync_fraction": SloSpec(warn=0.25, fail=0.5, unit="fraction"),
    # Byzantine invariants (sim/invariants.py): a single violation of
    # agreement, validity, or post-GST bounded-rounds liveness fails the
    # run — these are the properties the f<N/3 argument promises, and a
    # violating seed is replayable from its CHAOS-REPLAY line.
    "invariant_agreement": SloSpec(warn=0, fail=0, unit="violations"),
    "invariant_validity": SloSpec(warn=0, fail=0, unit="violations"),
    "invariant_bounded_rounds": SloSpec(warn=0, fail=0, unit="violations"),
    # Multi-process fleet (scripts/fleet.py): N real
    # validator processes over TCP under a concurrent proof-client
    # flood.  Chain divergence across processes and an uncut slowloris
    # socket are zero-tolerance; the proof-latency tail is bounded
    # loosely (1-core CI hosts serve hundreds of concurrent clients) and
    # tightened per-run by the harness flags.
    "fleet_diverged_chains": SloSpec(warn=0, fail=0, unit="nodes"),
    "fleet_slowloris_uncut": SloSpec(warn=0, fail=0, unit="sockets"),
    "fleet_proof_p99_ms": SloSpec(warn=10_000.0, fail=30_000.0, unit="ms"),
}


def slo_record(
    name: str,
    value: float,
    *,
    warn: Optional[float] = None,
    fail: Optional[float] = None,
    unit: Optional[str] = None,
    context: Optional[dict] = None,
) -> dict:
    """Build one SLO record (explicit limits override the table's)."""
    spec = DEFAULT_SLO_TABLE.get(name)
    record = {
        "slo": name,
        "value": value,
        "warn": warn if warn is not None else (spec.warn if spec else None),
        "fail": fail if fail is not None else (spec.fail if spec else None),
        "unit": unit if unit is not None else (spec.unit if spec else ""),
    }
    if context:
        record["context"] = context
    return record


def append_slo_records(path: Optional[str], records: Iterable[dict]) -> None:
    """Append records as JSONL (no-op on ``None`` path — soaks call this
    unconditionally and the env var decides whether evidence lands)."""
    if not path:
        return
    with open(path, "a") as fh:
        for record in records:
            fh.write(json.dumps(record) + "\n")


def parse_slo_records(path: str) -> List[dict]:
    """Parse one SLO JSONL file (lines without a ``slo`` key are skipped)."""
    records: List[dict] = []
    with open(path) as fh:
        for raw in fh:
            raw = raw.strip()
            if not raw.startswith("{"):
                continue
            try:
                line = json.loads(raw)
            except ValueError:
                continue
            if isinstance(line, dict) and "slo" in line:
                records.append(line)
    return records


def gate_slo_records(
    records: Iterable[dict],
    table: Optional[Dict[str, SloSpec]] = None,
) -> List[GateResult]:
    """Grade SLO records pass/warn/fail against absolute limits.

    Reuses :class:`GateResult` (and therefore :func:`render_table`):
    ``prior`` holds the fail limit, ``change_pct`` the headroom consumed.
    Records naming an SLO with no limits anywhere report ``info``.
    """
    table = DEFAULT_SLO_TABLE if table is None else table
    results: List[GateResult] = []
    for record in records:
        name = str(record.get("slo"))
        value = record.get("value")
        spec = table.get(name)
        higher = spec.higher_is_better if spec else False
        warn = record.get("warn", spec.warn if spec else None)
        fail = record.get("fail", spec.fail if spec else None)
        note = ""
        context = record.get("context")
        if context:
            note = str(context)[:60]
        if not isinstance(value, (int, float)):
            results.append(
                GateResult(
                    name, "slo", "warn", None, fail, "slo-limit", None,
                    note="record carries no numeric value",
                )
            )
            continue
        value = float(value)

        def breached(limit: Optional[float]) -> bool:
            if limit is None:
                return False
            return value < limit if higher else value > limit

        if breached(fail):
            status = "fail"
        elif breached(warn):
            status = "warn"
        elif warn is None and fail is None:
            status = "info"
        else:
            status = "pass"
        headroom = None
        if fail not in (None, 0):
            headroom = round(value / fail * 100.0, 1)
        results.append(
            GateResult(
                name,
                "slo",
                status,
                value,
                fail,
                "slo-limit",
                headroom,
                note=note,
            )
        )
    return results
