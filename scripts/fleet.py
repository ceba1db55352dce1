#!/usr/bin/env python
"""Multi-process fleet smoke: real validator processes, real sockets, SLO-gated.

The CI entry (`make fleet-smoke`) for the node
layer (ISSUE 19).  Launches N `python -m go_ibft_tpu.node` subprocesses
gossiping IBFT over TCP/gRPC, floods their proof APIs with a concurrent
client fleet plus churn/slowloris adversaries, then grades the run
through the SLO gates:

* missed_heights == 0 — every node finalized every height under flood;
* diverged_chains == 0 — the full-range proof is byte-identical from
  every node (agreement proven over the untrusted-client wire);
* slowloris_uncut == 0 — the header timeout cut every trickling socket;
* proof p99 / consensus finalize p99 latency bounds.

After the drain it reconstructs the cross-process consensus timeline
from the per-node trace exports and prints the critical-path report.
Exit 0 iff every gate held.

    python scripts/fleet.py [--nodes 4] [--heights 3] [--connections 64]
        [--seed 7] [--run-dir DIR] [--slo-out slo.jsonl]
        [--proof-p99-fail-ms N] [--finalize-p99-fail-ms N]
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(args, run_dir: str) -> int:
    from go_ibft_tpu.obs import gates, timeline
    from go_ibft_tpu.sim.fleet import FleetSpec, run_fleet

    spec = FleetSpec(
        nodes=args.nodes,
        heights=args.heights,
        connections=args.connections,
        churn_clients=args.churn_clients,
        slowloris_clients=args.slowloris_clients,
        seed=args.seed,
        think_s=args.think_s,
        header_timeout_s=args.header_timeout_s,
        min_flood_s=args.min_flood_s,
    )
    print(
        f"fleet: {spec.nodes} validator processes, {spec.connections} "
        f"client connections, {spec.churn_clients} churn + "
        f"{spec.slowloris_clients} slowloris adversaries, seed={spec.seed}"
    )
    result = run_fleet(spec, run_dir)
    print(json.dumps({"fleet": result.summary()}))
    print(result.replay_line)

    failures = []
    # The adversary contract: the server must have cut EVERY slowloris
    # socket it accepted (uncut sockets == capacity bleeding away).
    slow = result.slowloris
    slowloris_uncut = max(0, slow["opened"] - slow["cut_by_server"])
    records = [
        gates.slo_record(
            "missed_heights",
            result.missed_heights,
            context={"nodes": spec.nodes, "heights": spec.heights},
        ),
        gates.slo_record(
            "fleet_diverged_chains",
            result.diverged_chains,
            fail=0.0,
            context={"heads": result.heads},
        ),
        gates.slo_record(
            "fleet_slowloris_uncut",
            slowloris_uncut,
            fail=0.0,
            context=slow,
        ),
    ]
    if result.proof_p99_ms is None:
        failures.append("client fleet recorded no proof latencies")
    else:
        records.append(
            gates.slo_record(
                "fleet_proof_p99_ms",
                result.proof_p99_ms,
                fail=float(args.proof_p99_fail_ms),
                context={"proofs": result.proofs_total},
            )
        )
    if result.finalize_p99_ms is not None:
        records.append(
            gates.slo_record(
                "finalize_p99_ms",
                result.finalize_p99_ms,
                fail=float(args.finalize_p99_fail_ms),
            )
        )
    elif result.timeline_heights == 0:
        failures.append("cross-process timeline reconstructed 0 heights")
    gates.append_slo_records(args.slo_out, records)
    results = gates.gate_slo_records(records)
    print(gates.render_table(results))
    if any(r.status == "fail" for r in results):
        failures.append("SLO gate failed")

    if result.verified_proofs < spec.nodes and result.missed_heights == 0:
        failures.append(
            f"spot-verified {result.verified_proofs}/{spec.nodes} proofs"
        )
    for i, report in enumerate(result.reports):
        if not report:
            failures.append(f"node {i} emitted no drain report")

    # The cross-process critical-path report, from N separate processes'
    # trace files on one aligned clock.
    if result.trace_paths:
        files = [timeline.load_trace_file(p) for p in result.trace_paths]
        print()
        print(timeline.render_report(timeline.reconstruct(timeline.merge_events(files))))

    if failures:
        for failure in failures:
            print(f"FAIL: {failure}", file=sys.stderr)
        return 1
    print(
        f"\nfleet OK: {spec.nodes} processes finalized {result.heads} under "
        f"{result.peak_connections} concurrent connections, "
        f"{result.proofs_total} proofs served "
        f"({result.proofs_s:.1f}/s, p99 {result.proof_p99_ms}ms), "
        f"{result.timeline_heights} heights on the cross-process timeline"
    )
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--nodes", type=int, default=4)
    parser.add_argument("--heights", type=int, default=3)
    parser.add_argument("--connections", type=int, default=64)
    parser.add_argument("--churn-clients", type=int, default=2)
    parser.add_argument("--slowloris-clients", type=int, default=2)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--think-s", type=float, default=0.5)
    parser.add_argument("--header-timeout-s", type=float, default=1.0)
    parser.add_argument("--min-flood-s", type=float, default=2.0)
    parser.add_argument("--proof-p99-fail-ms", type=float, default=30_000.0)
    parser.add_argument("--finalize-p99-fail-ms", type=float, default=60_000.0)
    parser.add_argument(
        "--run-dir", default=None, help="keep configs/logs/traces here"
    )
    parser.add_argument(
        "--slo-out",
        default=os.environ.get("GO_IBFT_SLO_PATH"),
        help="append SLO records here (JSONL; default $GO_IBFT_SLO_PATH)",
    )
    args = parser.parse_args()
    if args.run_dir:
        os.makedirs(args.run_dir, exist_ok=True)
        return _run(args, args.run_dir)
    with tempfile.TemporaryDirectory() as tmp:
        return _run(args, tmp)


if __name__ == "__main__":
    sys.exit(main())
