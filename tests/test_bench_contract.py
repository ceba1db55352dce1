"""Contract tests for bench.py's evidence honesty.

The bench is the round's perf evidence pipeline; these pin the rules that
keep a degraded run from masquerading as a result (VERDICT r03 weak #3):

* the headline metric key is reserved for the intended (TPU) platform —
  a CPU fallback publishes an explicitly-degraded key instead;
* a fallback run carries a ``bench_error`` line flagging that nothing in
  it is TPU perf evidence, but still MEASURES every BASELINE.md config on
  the host route and exits 0 when all of them completed — rc != 0 is
  reserved for configs that actually crashed (VERDICT r5 weak #4);
* under driver conditions (``python bench.py`` in a fresh subprocess,
  default env, cold function caches) the 4-validator happy path must not
  regress vs the sequential host baseline (the r05 0.86x).
"""

import ast
import json
import pathlib
import subprocess
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench


def test_headline_key_reserved_for_target_platform():
    assert bench.headline_metric(False) == "prepare_commit_quorum_verify_p50_100v"
    assert bench.headline_metric(True) != bench.headline_metric(False)
    assert "fallback" in bench.headline_metric(True)


def test_fallback_flags_error_but_exits_by_evidence_and_crashes():
    """Static check: the fallback branch logs a 'bench_error' line (the
    degradation flag) yet exits 0 when every config produced evidence and
    none crashed — rc=0 is reserved STRICTLY for full evidence coverage
    (ISSUE 4), nonzero for crashes or evidence gaps, never for platform
    degradation alone."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "bench_error" in src
    # the degradation flag + evidence-driven exit are guarded by the
    # fallback flag; both branches route through the shared _finish
    assert "_FALLBACK" in src
    assert src.count("_finish(failures)") == 2
    finish_fn = next(
        n
        for n in tree.body
        if isinstance(n, ast.FunctionDef) and n.name == "_finish"
    )
    fsrc = ast.unparse(finish_fn)
    assert "sys.exit(1 if failures or missing else 0)" in fsrc
    assert "bench_evidence_gap" in fsrc


_FIVE_CONFIG_KEYS = (
    "happy_path_4v_height_latency",
    "ecdsa_1000v_10h_pipelined_throughput",
    "bls_aggregate_verify_p50_100v",
    "byzantine_300v_30pct_prepare_commit_p50",
    "chaos_degraded_overhead_100v",
    "chain_sustained_20h_100v",
    "mesh_sharded_drain_8k_100v",
    "aggregate_commit_cert_100v",
    "multi_tenant_blocks_per_s",
    "commit_critical_path_100v",
    "proof_serving_100v",
    "batched_multipairing_1000c",
    bench.headline_metric(True),
)


@pytest.fixture(scope="module")
def driver_run(tmp_path_factory):
    """ONE driver-conditions bench run shared by the contract asserts:
    fresh subprocess, cold function caches — what the round driver
    executes.  The CPU backend is pinned explicitly: these asserts pin the
    FALLBACK contract (the acceptance text says "on the CPU backend"), and
    on a host with a live TPU an unpinned run would take the non-fallback
    path — minutes of cold device compiles and a different line set.

    The run captures the full evidence surface: ``--trace`` exports the
    flight-recorder timeline and the evidence JSONL lands in a tmp dir."""
    import os

    tmp = tmp_path_factory.mktemp("bench_evidence")
    trace_path = tmp / "trace.json"
    evidence_path = tmp / "bench_evidence.jsonl"
    proc = subprocess.run(
        [sys.executable, "bench.py", "--trace", str(trace_path)],
        cwd=pathlib.Path(bench.__file__).parent,
        capture_output=True,
        text=True,
        timeout=600,
        # Child budget under the subprocess timeout: bench paces itself
        # against GO_IBFT_BENCH_BUDGET_S (default 720) and would otherwise
        # be killed mid-run by the 600s timeout on a host without the
        # native verifier, losing every diagnostic line.
        env=dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            GO_IBFT_BENCH_BUDGET_S="480",
            GO_IBFT_EVIDENCE_PATH=str(evidence_path),
        ),
    )
    lines = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    return (
        proc,
        {line["metric"]: line for line in lines if "metric" in line},
        {"trace": trace_path, "evidence": evidence_path},
    )


def test_driver_conditions_all_configs_measure(driver_run):
    """Every BASELINE.md config emits a MEASURED metric line on the CPU
    backend — no 'skipped on CPU fallback' placeholders (rounds 1-5 never
    saw configs #3-#5 complete on any backend), and rc is 0 because
    completing on a fallback platform is not a crash."""
    proc, by_metric, _ = driver_run
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for key in _FIVE_CONFIG_KEYS:
        line = by_metric.get(key)
        assert line is not None, f"no metric line for {key}: {proc.stdout}"
        assert line["value"] is not None, f"null value for {key}: {line}"
        assert "skipped" not in str(line.get("note", "")), line


def test_driver_conditions_config3_pipelined_packing_evidence(driver_run):
    """Config #3's host-routed line carries the packing/pipelining
    attribution fields and pins (a) pipelined-vs-sequential dispatch
    throughput and (b) a packing-throughput floor under driver conditions.

    Overlap needs parallel hardware: the pipelined leg runs packing on the
    main thread against a GIL-releasing bulk verify in a worker, so on a
    multi-CPU host the ratio must reach >= 1.0; on a single-CPU host, or
    without the native verifier (pure-Python verify holds the GIL, so the
    legs time-slice regardless of cores), the honest pin is "pipelining
    does not regress dispatch throughput" (>= 0.9 absorbs scheduler noise
    — the structural overlap itself is pinned hardware-independently by
    tests/test_pipeline_overlap.py with a timer-stub device).  The packing
    floor is pinned when the native verifier is present (the no-native
    path scales n down to 8, where per-call overhead dominates the
    lanes/s figure)."""
    _, by_metric, _ = driver_run
    line = by_metric["ecdsa_1000v_10h_pipelined_throughput"]
    assert line["pack_ms"] > 0, line
    assert "pipeline_speedup" in line and "overlap_efficiency" in line, line
    if line.get("cpus", 1) > 1 and line.get("native_verify"):
        assert line["pipeline_speedup"] >= 1.0, line
    else:
        assert line["pipeline_speedup"] >= 0.9, line
    if line.get("native_verify"):
        assert line["pack_lanes_per_s"] >= 25_000, line


def test_driver_conditions_config7_chain_evidence(driver_run):
    """Config #7's evidence schema (ISSUE 5): a MEASURED blocks/s line
    from a 20-height (6 without the native signer) 4-node ChainRunner
    cluster, carrying BOTH overlap variants and the per-height handoff
    attribution.  Handoff must stay well under a millisecond — the whole
    point of removing the per-height spawn/teardown barrier — and the
    chain must actually have sustained every height (the variants embed
    elapsed_s, so a null or partial run cannot masquerade)."""
    _, by_metric, _ = driver_run
    line = by_metric["chain_sustained_20h_100v"]
    assert line["unit"] == "blocks/s"
    assert line["value"] > 0
    for variant in ("overlap_on", "overlap_off"):
        sub = line[variant]
        assert sub["blocks_per_s"] > 0, line
        assert sub["handoff_ms_mean"] < 1.0, line
        assert "overlapped_lanes" in sub and "synced_heights" in sub, line
    assert line["heights"] in (6, 20)
    assert line["vs_baseline"] is not None


def test_driver_conditions_config8_mesh_evidence(driver_run):
    """Config #8's evidence schema (ISSUE 6): one line carrying MEASURED
    sharded AND single-device routes plus the mesh provenance fields
    (``mesh_devices``/``lanes_per_device``/``reduce_ms``) — on the
    no-device-work CPU fallback both routes are host-measured and the
    sharded one is explicitly labeled degraded (``mesh_devices`` 1), never
    silently dropped.  The ``devices`` stamp (probe fingerprint device
    count) distinguishes dp=1 from dp>1 evidence."""
    _, by_metric, paths = driver_run
    line = by_metric["mesh_sharded_drain_8k_100v"]
    assert line["unit"] == "lanes/s"
    assert line["value"] > 0
    for field in ("mesh_devices", "lanes_per_device", "reduce_ms", "lanes"):
        assert field in line, (field, line)
    routes = line["routes"]
    assert "single_device" in routes
    assert routes["single_device"]["lanes_per_s"] > 0
    sharded = [k for k in routes if k.startswith("dp") or k == "sharded"]
    assert sharded, routes
    measured = [k for k in sharded if "lanes_per_s" in routes[k]]
    assert measured, routes  # the sharded route is measured, even degraded
    # the evidence file's line carries the probed device count stamp
    with open(paths["evidence"]) as fh:
        evidence = [
            json.loads(ln)
            for ln in fh
            if json.loads(ln).get("config") == "mesh_sharded_drain_8k_100v"
        ]
    assert len(evidence) == 1
    assert "devices" in evidence[0]


def test_driver_conditions_config9_aggregate_evidence(driver_run):
    """Config #9's evidence schema (ISSUE 7): a MEASURED aggregate-COMMIT
    line on the CPU fallback carrying the aggregate-vs-per-seal ratio,
    the O(1) certificate size, the pairing p50, and the tree fan-in; the
    ops counts pin the acceptance claim (1 pairing equation + aggregation
    vs a quorum of recovers at 100 validators), the bisect sub-record
    pins oracle-exact verdicts on the seeded Byzantine mix, and the tree
    sub-record pins per-node COMMIT bytes under the flooding share."""
    _, by_metric, _ = driver_run
    line = by_metric["aggregate_commit_cert_100v"]
    assert line["value"] > 0
    for field in ("ratio", "cert_bytes", "pairing_ms", "fan_in", "quorum"):
        assert field in line, (field, line)
    assert line["vs_baseline"] == line["ratio"]
    ops = line["verify_ops"]
    assert ops["aggregate_pairing_eqs"] == 1
    assert ops["per_seal_recovers"] == (2 * line["validators"]) // 3 + 1
    assert ops["aggregate_pairing_eqs"] < ops["per_seal_recovers"]
    # O(1) evidence: header + hash + one G2 point + 1 bit per validator
    assert line["cert_bytes"] == 15 + 32 + 192 + (line["validators"] + 7) // 8
    bisect = line["bisect"]
    assert bisect["oracle_exact"] is True
    assert bisect["equations"] > 1
    if line["quorum"] > 8:  # the saving claim needs a real committee
        assert bisect["equations"] < line["quorum"]
    tree = line["tree"]
    assert tree["max_commit_bytes_per_node"] < tree["flood_bytes_per_node"]


def test_driver_conditions_config10_multitenant_evidence(driver_run):
    """Config #10's evidence schema (ISSUE 8): a MEASURED aggregate-vs-
    serial multi-tenant line from >=8 concurrent real-crypto chains
    sharing ONE TenantScheduler — the ``tenants`` / ``aggregate_blocks_
    per_s`` / ``serial_blocks_per_s`` / ``coalesce_ratio`` / per-tenant
    p99 fields the acceptance names, plus the honesty gates: oracle-exact
    coalesced verdicts, and ZERO starved chains in both variants (every
    chain finalized every height — a tenant crowded off the scheduler
    fails here, it does not vanish into an average)."""
    _, by_metric, _ = driver_run
    line = by_metric["multi_tenant_blocks_per_s"]
    assert line["unit"] == "blocks/s"
    assert line["value"] > 0
    assert line["tenants"] >= 8
    for field in (
        "aggregate_blocks_per_s",
        "serial_blocks_per_s",
        "coalesce_ratio",
        "per_chain_p99_ms",
        "per_tenant_p99_ms",
        "per_tenant_p50_ms",
    ):
        assert field in line, (field, line)
    assert line["aggregate_blocks_per_s"] == line["value"]
    assert line["serial_blocks_per_s"] > 0
    assert line["vs_baseline"] == pytest.approx(
        line["aggregate_blocks_per_s"] / line["serial_blocks_per_s"], rel=1e-2
    )
    # Coalescing must actually have happened: strictly more requests than
    # shared dispatches across the concurrent run.
    assert line["coalesce_ratio"] is not None and line["coalesce_ratio"] > 1.0
    assert line["oracle_exact"] is True
    assert line["starved"] == 0
    # Every chain's p99 is reported (the per-tenant latency SLO evidence).
    assert len(line["per_chain_p99_ms"]) == line["tenants"]
    assert all(v > 0 for v in line["per_chain_p99_ms"].values())


def test_driver_conditions_config11_critical_path_evidence(driver_run):
    """Config #11's evidence schema (ISSUE 9): a MEASURED accept->
    finalize latency comparison with speculation + early-exit ON vs OFF
    under byte-identical arrival schedules, on the host route.  Floor
    pins: the speculation plane actually engaged (hit rate > 0), the
    early-exit actually skipped lanes on the 100v workload, both
    variants' p50/p99 are present, and every finalized seal set was
    oracle-gated."""
    _, by_metric, _ = driver_run
    line = by_metric["commit_critical_path_100v"]
    assert line["value"] > 0
    assert line["route"] == "host"
    for field in (
        "p50_ms_off",
        "p50_ms_on",
        "p99_ms_off",
        "p99_ms_on",
        "quorum",
        "validators",
    ):
        assert field in line and line[field] is not None, (field, line)
    assert line["vs_baseline"] == pytest.approx(
        line["p50_ms_off"] / line["p50_ms_on"], rel=1e-2
    )
    # The speculation cache served real hits and the early-exit drains
    # really skipped lanes (the two mechanisms the config measures).
    assert line["speculated_lanes"] > 0
    assert line["speculation_hits"] > 0
    assert line["speculation_hit_rate"] > 0
    assert line["early_exit_lanes_skipped"] > 0
    assert line["oracle_exact"] is True
    assert line["heights"] > 0


def test_driver_conditions_config12_proof_serving_evidence(driver_run):
    """Config #12's evidence schema (ISSUE 10): a MEASURED proof-serving
    line carrying the acceptance fields — warm-cache proofs/s >= 5x cold,
    coalesced multi-client verification >= 1.5x per-client-sequential on
    the same schedule, oracle-gated lane verdicts, and the QoS bound (a
    live consensus chain missing ZERO heights under the read-tier proof
    flood) — plus the cache-hit / coalesce attribution fields the
    regression gates read."""
    _, by_metric, _ = driver_run
    line = by_metric["proof_serving_100v"]
    assert line["unit"] == "proofs/s"
    assert line["value"] > 0
    for field in (
        "cold_proofs_per_s",
        "warm_proofs_per_s",
        "warm_over_cold",
        "coalesced_proofs_per_s",
        "per_client_proofs_per_s",
        "coalesce_speedup",
        "cache_hit_rate",
        "sig_cache_hit_rate",
        "sched_dispatches",
        "lanes_per_proof",
    ):
        assert field in line and line[field] is not None, (field, line)
    # the two acceptance ratios, as measured under driver conditions
    assert line["warm_over_cold"] >= 5.0, line
    assert line["coalesce_speedup"] >= 1.5, line
    assert line["vs_baseline"] == line["coalesce_speedup"]
    assert line["value"] == line["coalesced_proofs_per_s"]
    assert line["clients"] >= 4
    # the QoS hard bound: the concurrent consensus chain missed nothing
    qos = line["qos"]
    assert qos["missed_heights"] == 0
    assert qos["chain_heights"] > 0 and qos["chain_nodes"] >= 4
    assert qos["flood_proofs"] > 0  # and the read tier still progressed
    assert line["oracle_exact"] is True


def test_driver_conditions_config13_multipair_evidence(driver_run):
    """Config #13's evidence schema (ISSUE 12): a MEASURED batched-vs-
    sequential multi-pairing line — N certificates through ONE batched
    dispatch (the dispatch count is part of the line) against the
    per-cert aggregate_check loop, verdicts oracle-gated on a seeded
    corrupt set BEFORE timing, with the committee-size sweep dict that
    finally gives config #9's chip-blocked device_sizes a host-route
    measurement.  The >=5x acceptance floor is asserted inside the
    config itself whenever it runs >= 8 lanes."""
    _, by_metric, _ = driver_run
    line = by_metric["batched_multipairing_1000c"]
    assert line["value"] > 0
    for field in (
        "ratio",
        "certs",
        "sequential_ms",
        "batched_ms",
        "dispatches",
        "lanes_per_dispatch",
        "route",
        "committee_sizes",
    ):
        assert field in line, (field, line)
    assert line["vs_baseline"] == line["ratio"]
    assert line["dispatches"] == 1
    assert line["lanes_per_dispatch"] == line["certs"]
    assert line["oracle_exact"] is True
    assert line["corrupt_gate"]["oracle_exact"] is True
    assert line["corrupt_gate"]["corrupted"] >= 2
    if line["certs"] >= 8:
        assert line["ratio"] >= 5.0
    # the sweep dict exists; entries are either measured or explicitly
    # budget-skipped (never silently absent)
    for size, entry in line["committee_sizes"].items():
        assert ("host_agg_ms" in entry) or ("skipped" in entry.get("note", "")), (
            size,
            entry,
        )


def test_multipair_only_flag_scopes_evidence_contract():
    """`bench.py --multipair-only` (the make multipair-bench entry) runs
    ONLY config #13 and scopes the rc=0 evidence contract to it — static
    check on _run, like the other --*-only pins."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "multipair_only" in src
    assert "config13_multipair" in src


def test_cluster_only_flag_scopes_evidence_contract():
    """`bench.py --cluster-only` (the make cluster-bench entry) runs
    ONLY config #15 and scopes the rc=0 evidence contract to it — static
    check on _run, like the other --*-only pins.  Config #15 is NOT in
    the driver-conditions measured set: under the 480 s budget it skips
    with an honest evidence line (config #14 precedent) and the scoped
    entry point is where it measures."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "cluster_only" in src
    assert "config15_cluster" in src


def test_serve_only_flag_scopes_evidence_contract():
    """`bench.py --serve-only` (the make serve-bench entry) runs ONLY
    config #12 and scopes the rc=0 evidence contract to it — static
    check on _run, like the --mesh-only / --tenant-only / --latency-only
    pins."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "serve_only" in src
    assert "config12_proof_serving" in src


def test_latency_only_flag_scopes_evidence_contract():
    """`bench.py --latency-only` (the make latency-smoke entry) runs
    ONLY config #11 and scopes the rc=0 evidence contract to it —
    static check on _run, like the --mesh-only / --tenant-only pins."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "latency_only" in src
    assert "config11_commit_critical_path" in src


def test_tenant_only_flag_scopes_evidence_contract():
    """`bench.py --tenant-only` (the make tenant-bench entry) runs ONLY
    config #10 and scopes the rc=0 evidence contract to it — static check
    on _run, like the --mesh-only pin."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "tenant_only" in src
    assert "config10_multitenant" in src


def test_mesh_only_flag_scopes_evidence_contract():
    """`bench.py --mesh-only` (the make mesh-bench entry) runs ONLY config
    #8 and scopes the rc=0 evidence contract to it — static check on _run,
    so the full-matrix schedules cannot silently absorb the flag."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "mesh_only" in src
    assert "config8_mesh" in src


def test_driver_conditions_happy_path_parity(driver_run):
    """The parity acceptance metric, pinned under driver conditions: the
    adaptive engine must at least break even against the forced sequential
    host cluster (>= 0.95x; r05 recorded 0.86x before the ingress-window
    and measurement-discipline fixes)."""
    _, by_metric, _ = driver_run
    line = by_metric["happy_path_4v_height_latency"]
    assert line["vs_baseline"] >= 0.95, line


def test_guarded_skips_config_when_budget_reserved(monkeypatch, capsys):
    """A config whose start would eat the reserve for later configs (the
    headline above all) is SKIPPED with an explicit note line, not
    started — a started config that outruns the driver budget loses every
    line after it (BENCH_r04.json rc=124)."""
    calls = []

    def config():
        calls.append(1)

    config.metric = "some_secondary_metric"
    monkeypatch.setattr(bench, "_BUDGET_S", 0.0)  # budget already gone
    failures = []
    bench._guarded(config, failures, reserve_s=10.0)
    out = capsys.readouterr().out
    assert calls == []  # never started
    assert failures == []
    assert "some_secondary_metric" in out and "skipped" in out
    assert '"error"' not in out  # a budget skip is not an error line

    # with budget available the config runs
    monkeypatch.setattr(bench, "_BUDGET_S", 10**9)
    bench._guarded(config, failures, reserve_s=10.0)
    assert calls == [1]


def test_driver_conditions_evidence_schema(driver_run):
    """The evidence JSONL contract (ISSUE 4 satellite): exactly one
    append-only line per BASELINE.md config (diagnostics lines ride along
    but never replace one), every line carrying the required schema
    fields, with backend/probe provenance matching the CPU-pinned run.
    Timestamps are monotone non-decreasing — the flush-per-record
    append-only discipline observable from the artifact itself."""
    from go_ibft_tpu.obs.evidence import REQUIRED_EVIDENCE_FIELDS

    _, _, artifacts = driver_run
    raw = artifacts["evidence"].read_text().splitlines()
    lines = [json.loads(line) for line in raw if line.strip()]
    assert lines, "evidence file is empty"
    by_config = {}
    for line in lines:
        for field in REQUIRED_EVIDENCE_FIELDS:
            assert field in line, (field, line)
        assert line["backend"] == "cpu-fallback", line
        assert line["probe"] in ("ok", "cached", "timeout", "error"), line
        by_config.setdefault(line["config"], []).append(line)
    for key in _FIVE_CONFIG_KEYS:
        assert key in by_config, (key, sorted(by_config))
        assert len(by_config[key]) == 1, by_config[key]
    ts = [line["ts"] for line in lines]
    assert ts == sorted(ts)


def test_driver_conditions_trace_covers_every_drain(driver_run):
    """``bench.py --trace`` emits a Chrome-trace JSON (schema-validated)
    whose spans cover pack -> dispatch -> device-wait -> quorum for EVERY
    verify drain of the run — config #1's happy path included (the
    acceptance criterion's named phases, on the host route exactly like
    the device route)."""
    from tests.test_obs import _validate_trace_doc

    _, _, artifacts = driver_run
    doc = _validate_trace_doc(json.loads(artifacts["trace"].read_text()))
    # The ring must not have wrapped: a truncated window orphans spans at
    # the boundary, and the per-drain containment below is only meaningful
    # over a complete record.
    assert doc["otherData"]["droppedRecords"] == 0
    events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    by_tid = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append(e)
    drains = [e for e in events if e["name"] == "verify.drain"]
    assert drains, "no verify.drain spans recorded"
    phases = {
        "verify.pack",
        "verify.dispatch",
        "verify.device_wait",
        "verify.quorum",
    }
    for drain in drains:
        t0, t1 = drain["ts"], drain["ts"] + drain["dur"]
        inside = {
            e["name"]
            for e in by_tid[drain["tid"]]
            if e["ph"] == "X"
            and e["name"] in phases
            and e["ts"] >= t0
            and e["ts"] + e["dur"] <= t1
        }
        assert inside == phases, (drain, inside)
    # The engine phases render too: per-node tracks with round markers.
    names = {e["name"] for e in events}
    assert {"round.start", "prepare.drain", "commit.drain"} <= names


def test_disabled_tracing_overhead_under_5pct(driver_run):
    """The bench-contract pin on disabled-mode overhead: the driver run
    above measured the happy path; a height crosses ~250 span sites
    (counted from the traced run's events-per-height), so the per-site
    disabled cost measured here must keep the instrumentation tax under
    5% of the recorded height latency."""
    import time as _time

    from go_ibft_tpu.obs import trace as obs_trace

    assert not obs_trace.enabled()
    n = 100_000
    t0 = _time.perf_counter()
    for _ in range(n):
        with obs_trace.span("bench.overhead", lanes=4):
            pass
    per_call_s = (_time.perf_counter() - t0) / n
    _, by_metric, _ = driver_run
    height_ms = by_metric["happy_path_4v_height_latency"]["value"]
    spans_per_height = 250
    overhead = per_call_s * spans_per_height
    assert overhead < 0.05 * height_ms / 1e3, (
        f"disabled tracing costs {overhead * 1e3:.3f}ms per ~{height_ms}ms "
        f"height ({per_call_s * 1e9:.0f}ns/site x {spans_per_height} sites)"
    )


def test_disabled_metrics_overhead_under_5pct(driver_run):
    """ISSUE 11 coverage satellite: the fixed-bucket histogram sites
    mirror the tracer's disabled posture — one predicate, no clock reads
    — so the combined instrumentation tax of the new seams (accept ->
    finalize, verify drains, sched drains, WAL appends, proof serving)
    stays under 5% of the config #1 happy-path height.  A height crosses
    far fewer histogram sites than span sites (they are per-drain, not
    per-phase-step); 50 is a generous ceiling."""
    import time as _time

    from go_ibft_tpu.utils import metrics as _metrics

    assert not _metrics.fixed_histograms_enabled()
    n = 100_000
    t0 = _time.perf_counter()
    for _ in range(n):
        _metrics.observe_fixed(("go-ibft", "latency", "bench_overhead_ms"), 1.0)
    per_call_s = (_time.perf_counter() - t0) / n
    assert _metrics.fixed_histograms_snapshot() == {}  # truly off
    _, by_metric, _ = driver_run
    height_ms = by_metric["happy_path_4v_height_latency"]["value"]
    sites_per_height = 50
    overhead = per_call_s * sites_per_height
    assert overhead < 0.05 * height_ms / 1e3, (
        f"disabled histograms cost {overhead * 1e3:.3f}ms per ~{height_ms}ms "
        f"height ({per_call_s * 1e9:.0f}ns/site x {sites_per_height} sites)"
    )


def test_disabled_ledger_overhead_under_5pct(driver_run):
    """ISSUE 14 coverage satellite: the cost-ledger seams mirror the
    tracer's disabled posture — one predicate, a shared no-op span, no
    numpy or clock reads — so the instrumentation tax of the dispatch
    seams (verify pack/dispatch/readback, sched flushes, aggregate
    merges, ops pairing entry points) stays under 5% of the config #1
    happy-path height.  A height crosses far fewer ledger sites than
    span sites (one per dispatch, not per phase step); 50 is generous."""
    import time as _time

    from go_ibft_tpu.obs import ledger as _ledger

    assert not _ledger.enabled()
    n = 100_000
    t0 = _time.perf_counter()
    for _ in range(n):
        with _ledger.dispatch_span(
            "quorum_certify", route="device", live=4, padded=8
        ):
            pass
        _ledger.add_device_ms("quorum_certify", "device", 1.0)
    per_call_s = (_time.perf_counter() - t0) / n
    assert _ledger.snapshot() is None  # truly off
    _, by_metric, _ = driver_run
    height_ms = by_metric["happy_path_4v_height_latency"]["value"]
    sites_per_height = 50
    overhead = per_call_s * sites_per_height
    assert overhead < 0.05 * height_ms / 1e3, (
        f"disabled ledger costs {overhead * 1e3:.3f}ms per ~{height_ms}ms "
        f"height ({per_call_s * 1e9:.0f}ns/site x {sites_per_height} sites)"
    )


def test_driver_run_stamps_ledger_blocks_on_evidence(driver_run):
    """The evidence-line ledger block schema pin (ISSUE 14 satellite):
    bench runs with the cost ledger ON, so every config's evidence line
    carries a delta block with the pinned keys, the run emits a
    cost_ledger summary line, and the configs that drive batched device
    or host dispatches report nonzero dispatch counts."""
    proc, by_metric, paths = driver_run
    lines = [
        json.loads(raw)
        for raw in pathlib.Path(paths["evidence"]).read_text().splitlines()
        if raw.startswith("{")
    ]
    config_lines = [
        line for line in lines if line.get("metric") in _FIVE_CONFIG_KEYS
    ]
    assert config_lines
    block_keys = {
        "dispatches",
        "live_lanes",
        "padded_lanes",
        "device_ms",
        "compiles",
        "compile_ms",
        "occupancy",
    }
    for line in config_lines:
        assert "ledger" in line, f"no ledger block on {line['metric']}"
        assert block_keys <= set(line["ledger"]), line["metric"]
    # The batched multi-pairing config issues real (host-route) ledger
    # dispatches — its block must show them.
    mp = next(
        line
        for line in config_lines
        if line["metric"] == "batched_multipairing_1000c"
    )
    assert mp["ledger"]["dispatches"] > 0
    summary = by_metric.get("cost_ledger")
    assert summary is not None and summary["value"] > 0
    assert summary["path"]


def test_byzantine_only_flag_scopes_evidence_contract():
    """`bench.py --byzantine-only` (the make byzantine-smoke entry) runs
    ONLY config #16 and scopes the rc=0 evidence contract to it — static
    check on _run, like the other --*-only pins.  Like #15, config #16
    carries a driver-schedule reserve so under the default budget it
    skips with an honest evidence line and the scoped entry point is
    where it measures."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "byzantine_only" in src
    assert "config16_byzantine_soak" in src


def test_byzantine_soak_schedule_membership_and_schema():
    """Config #16's driver contract: it sits in BOTH schedules, owns the
    byzantine_soak_100v metric key, gates invariants and liveness BEFORE
    publishing timing, emits the replayable CHAOS-REPLAY artifact, and
    routes the clean/degraded overhead ratio through obs/gates.py."""
    import inspect

    from go_ibft_tpu.obs import gates

    for schedule in (bench._FALLBACK_SCHEDULE, bench._DEVICE_SCHEDULE):
        assert any(
            fn.__name__ == "config16_byzantine_soak" for fn, _ in schedule
        ), "config16 missing from a driver schedule"
    assert bench.config16_byzantine_soak.metric == "byzantine_soak_100v"
    src = inspect.getsource(bench.config16_byzantine_soak)
    # replay artifact + invariant/liveness gates precede the evidence line
    for needle in (
        "cluster_replay_line",
        "missed_heights",
        "summary",
        "gate_slo_records",
        "byzantine_soak_overhead_x",
        "AdversaryMix.seeded",
    ):
        assert needle in src, f"config16 lost its {needle} step"
    assert src.index("gate_slo_records") < src.index("_log(")
    # the overhead ratio and the invariant counters are SLO-gated keys
    assert "byzantine_soak_overhead_x" in gates.DEFAULT_SLO_TABLE
    for inv in ("agreement", "validity", "bounded_rounds"):
        spec = gates.DEFAULT_SLO_TABLE[f"invariant_{inv}"]
        assert spec.warn == 0 and spec.fail == 0, (
            "invariant SLOs must have zero tolerance"
        )


def test_fleet_only_flag_scopes_evidence_contract():
    """`bench.py --fleet-only` (the make fleet-bench entry) runs ONLY
    config #17 and scopes the rc=0 evidence contract to it — static
    check on _run, like the other --*-only pins.  The config launches
    real subprocesses, so like #15/#16 it carries a driver-schedule
    reserve and the scoped entry point is where it measures."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "fleet_only" in src
    assert "config17_fleet" in src


def test_fleet_schedule_membership_and_schema():
    """Config #17's driver contract: it sits in BOTH schedules, owns the
    multiprocess_fleet metric key, QoS-gates (missed heights, chain
    divergence over the wire, slowloris cut rate) BEFORE publishing
    proofs/s, emits the replayable CHAOS-REPLAY artifact, and its SLO
    families carry standing limits in obs/gates.py."""
    import inspect

    from go_ibft_tpu.obs import gates

    for schedule in (bench._FALLBACK_SCHEDULE, bench._DEVICE_SCHEDULE):
        assert any(
            fn.__name__ == "config17_fleet" for fn, _ in schedule
        ), "config17 missing from a driver schedule"
    assert bench.config17_fleet.metric == "multiprocess_fleet"
    src = inspect.getsource(bench.config17_fleet)
    for needle in (
        "run_fleet",
        "missed_heights",
        "fleet_diverged_chains",
        "fleet_slowloris_uncut",
        "gate_slo_records",
        "replay_line",
        "verified_proofs",
        "timeline_heights",
    ):
        assert needle in src, f"config17 lost its {needle} step"
    # QoS gate precedes the evidence line
    assert src.index("gate_slo_records") < src.index("_log(")
    # zero-tolerance standing limits for the safety-shaped families
    for key in ("fleet_diverged_chains", "fleet_slowloris_uncut"):
        spec = gates.DEFAULT_SLO_TABLE[key]
        assert spec.warn == 0 and spec.fail == 0
    assert gates.DEFAULT_SLO_TABLE["fleet_proof_p99_ms"].fail is not None


def test_checkpoint_only_flag_scopes_evidence_contract():
    """`bench.py --checkpoint-only` (the make checkpoint-smoke entry)
    runs ONLY config #18 and scopes the rc=0 evidence contract to it —
    static check on _run, like the other --*-only pins."""
    tree = ast.parse(pathlib.Path(bench.__file__).read_text())
    run_fn = next(
        n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_run"
    )
    src = ast.unparse(run_fn)
    assert "checkpoint_only" in src
    assert "config18_checkpoint_sync" in src


def test_checkpoint_schedule_membership_and_schema():
    """Config #18's driver contract (ISSUE 20): it sits in BOTH
    schedules, owns the checkpoint_sync_1m metric key, measures the
    O(log n) cold sync against the linear baseline with a real-crypto
    rotation + wire-path splice attack, and gates the dispatch-count
    and bytes-ratio SLOs BEFORE publishing the evidence line."""
    import inspect

    for schedule in (bench._FALLBACK_SCHEDULE, bench._DEVICE_SCHEDULE):
        assert any(
            fn.__name__ == "config18_checkpoint_sync" for fn, _ in schedule
        ), "config18 missing from a driver schedule"
    assert bench.config18_checkpoint_sync.metric == "checkpoint_sync_1m"
    src = inspect.getsource(bench.config18_checkpoint_sync)
    for needle in (
        "cold_sync",
        "skip_path",
        "lazy_sign",
        "embed_next_set",
        "require_commitments",
        "splice",
        "next-set root",
        "pairing_dispatches",
        "checkpoint_sync_dispatches",
        "checkpoint_real_sync_dispatches",
        "checkpoint_bytes_fraction_of_linear",
        "gate_slo_records",
    ):
        assert needle in src, f"config18 lost its {needle} step"
    # the SLO gate precedes the evidence line
    assert src.index("gate_slo_records") < src.index("_log(")
