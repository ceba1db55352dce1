"""CPU rehearsals of the benchmark's control flow, at tiny sizes, through
the drivers' own functions with the platform check patched HERE (the command
has no CPU mode).  No number these runs print is a device number."""

import contextlib
import gc
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402  (benchmark/run.py)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _tiny(validators: int, lanes: int, **mix_overrides):
    """``load_cell`` with the deployment cut to a size the CPU runs in
    seconds: the committee, the ranges and the pre-signing budget."""
    real = bench_run.load_cell

    def load(name, root=bench_run.ROOT):
        cell = real(name, root)
        cfg, mix = cell["config"], cell["traffic"]
        cfg["validators"] = validators
        cfg["quorum"] = 2 * validators // 3 + 1
        cfg["assumed"]["seals_per_block"] = min(cfg["assumed"]["seals_per_block"], validators)
        mix.update(lanes_per_call=lanes, check_lanes=lanes, presign_msgs_per_s=2400, trace_seconds=0.3)
        mix.update(mix_overrides)
        return cell

    return load


def _run(monkeypatch, workload: str, trace: int, seconds: float, validators=4, lanes=32, **mix_overrides):
    import jax

    monkeypatch.setattr(
        bench_run,
        "require_tpu",
        lambda chips: {
            "platform": jax.devices()[0].platform,
            "kind": "TPU v5 lite",
            "count": 1,
        },
    )
    monkeypatch.setattr(bench_run, "load_cell", _tiny(validators, lanes, **mix_overrides))
    out = io.StringIO()
    args = bench_run.argparse.Namespace(
        workload=workload, seed=5, seconds=seconds, trace=trace
    )
    try:
        with contextlib.redirect_stdout(out):
            rc = bench_run.run(args)
    finally:
        gc.unfreeze()
        # This run's trace and no other worker's: another file may be mid-trace.
        shutil.rmtree(os.path.join(ROOT, ".cache", "benchmark_trace", workload), ignore_errors=True)
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    return rc, lines


def test_round0_at_four_validators_finalizes_and_checks(monkeypatch):
    rc, lines = _run(monkeypatch, "ecdsa-100v.flood", 0, 1.0)
    result, info = lines[-1], lines[-2]
    assert rc == 0 and set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 5
    assert set(result["metrics"]) == {"finalize_p50_ms", "finalize_p90_ms", "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert result["device"]["platform"] == "cpu"  # named, never passed off as a chip
    assert info["checked"] is True and info["heights_checked"] == result["attempted"]
    assert info["heights_oracle_checked"] >= 1  # the sequential host oracle ran
    assert list(result)[-1] == "compared" and all(c["value"] == 0 == c["limit"] for c in result["compared"].values())
    assert set(result["compared"]) == {"operations_failed", "compiles_in_window", "guarantees_broken", "heights_against_their_labels"}
    assert info["cutover"] == 16 and info["cutover_source"] == "default"
    assert info["native_signer"] in (True, False)
    # One throw-away height went through the ingress and the engine before
    # the three timed warm heights (PR 33), and it finalized.
    assert len(info["throw_away_heights_ms"]) == 1 and info["throw_away_failed"] == 0
    assert len(info["warm_heights_ms"]) == 3
    assert info["presigned_heights"] == 1 + 3 + 300  # 1 s at 2400 messages, 8 a height


def test_round0_traced_reports_the_layer_metrics(monkeypatch):
    rc, lines = _run(monkeypatch, "ecdsa-100v.flood", 1, 1.0)
    result = lines[-1]
    assert rc == 0 and set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True
    got = result["metrics"]
    assert {"msgs_per_flush", "dispatches_per_height", "engine_self_ms",
            "device_lane_share", "compiles_in_window", "warm_s"} <= set(got)
    assert "finalize_p50_ms" not in got  # traced runs report per-layer metrics
    assert got["device_lane_share"]["value"] == 0.0  # 3-message batches: the host route
    assert got["msgs_per_flush"]["value"] >= 1
    assert got["compiles_in_window"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # The node proposes one height in four: those samples are kept too.
    assert result["attempted"] >= 4


def test_sync_at_small_ranges_checks_masks_and_refuses_sub_quorum(monkeypatch):
    rc, lines = _run(monkeypatch, "ecdsa-4v.sync", 1, 1.0, validators=4, lanes=32)
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    got = result["metrics"]
    assert got["sync_device_lane_share"]["value"] == 100.0  # 32 lanes: the device route
    assert got["sync_lane_occupancy"]["value"] == 100.0
    assert "sync_pack_ms_per_chunk" in got and "sync_host_ms_per_call" in got
    assert info["blocks_per_call"] == 8 and info["lanes_per_call"] == 32
    assert info["check_invalid_lanes"] >= 1  # the mask had something to show
    assert "quorum" in info["sub_quorum_refused"]


def test_sync_plain_run_reports_the_rate(monkeypatch):
    rc, lines = _run(monkeypatch, "ecdsa-100v.sync", 0, 0.5, validators=4, lanes=32)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"sync_sigs_per_s", "setup_s"}
    assert result["metrics"]["sync_sigs_per_s"]["unit"] == "sigs/s"


def test_a_wrong_mask_makes_the_run_incorrect(monkeypatch):
    import numpy as np

    from go_ibft_tpu.verify import AdaptiveBatchVerifier

    real = AdaptiveBatchVerifier.verify_seal_lanes

    def all_true(self, lanes, height):
        return np.ones(len(real(self, lanes, height)), dtype=bool)

    monkeypatch.setattr(AdaptiveBatchVerifier, "verify_seal_lanes", all_true)
    rc, lines = _run(monkeypatch, "ecdsa-4v.sync", 0, 0.3, validators=4, lanes=32)
    assert rc == 0 and lines[-1]["correct"] is False
    assert "mask mismatch" in lines[-2]["guarantee_broken"]
    # Every number compared stands beside its limit, last in the line.
    compared = lines[-1]["compared"]
    assert list(lines[-1])[-1] == "compared" and compared["guarantees_broken"] == {"value": 1, "limit": 0}
    assert compared["mask_lanes_off_the_labels"]["value"] >= 1
    assert compared["oracle_lanes_off_the_labels"] == {"value": 0, "limit": 0}


def test_command_without_a_tpu_exits_nonzero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "ecdsa-100v.flood",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == bench_run.NO_ACCELERATOR_RC
    assert "'cpu'" in proc.stderr and "no CPU mode" in proc.stderr
    assert not [line for line in proc.stdout.splitlines() if line.startswith("{")]


def test_signing_workers_and_committee_code_leave_jax_out():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.lib.signing import SigningPool\n"
        "import benchmark.lib.committee, benchmark.lib.stats, benchmark.lib.harness\n"
        "if __name__ == '__main__':\n"
        "    pool = SigningPool(workers=1)\n"
        "    pool.submit_flood(4, 1, [1, 2], 0)\n"
        "    pool.submit_sync(4, 1, 1, 2, {'seals_per_block': 4, 'corrupt_every': 0, 'corrupt_seals': 0})\n"
        "    jobs, native = pool.collect()\n"
        "    pool.close()\n"
        "    print(len(jobs), 'jax' in sys.modules)\n"
    ) % ROOT
    path = os.path.join(ROOT, ".cache", "benchmark_sign_probe.py")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(code)
    try:
        proc = subprocess.run(
            [sys.executable, path], capture_output=True, text=True, timeout=120, cwd=ROOT
        )
    finally:
        os.remove(path)
    # collect() raises where a worker had imported jax; the parent stayed clean too.
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["4", "False"]  # 2 + 2 jobs; no jax in the parent


def test_new_cell_mix_and_span_metric_are_picked_up_as_new_files(tmp_path):
    """A later PR adds files and entries and edits no file that is there."""
    root = tmp_path / "copy"
    shutil.copytree(HERE, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {
        os.path.relpath(os.path.join(d, f), root): open(os.path.join(d, f), "rb").read()
        for d, _s, fs in os.walk(root) for f in fs
    }
    # a configuration, a mix on the existing driver, a span-sourced metric
    cfg = json.load(open(root / "benchmark/configs/ecdsa-4v.json"))
    cfg.update(name="ecdsa-7v", validators=7, quorum=5)
    json.dump(cfg, open(root / "benchmark/configs/ecdsa-7v.json", "w"))
    mix = json.load(open(root / "benchmark/traffic/sync.json"))
    mix.update(name="sync-short", lanes_per_call=2048)
    json.dump(mix, open(root / "benchmark/traffic/sync-short.json", "w"))
    metric = {
        "name": "sync_quorum_ms_per_drain", "layer": "verifier policy", "unit": "ms",
        "better": "lower", "source": "program_span", "moves": "sync_sigs_per_s",
        "read": {"kind": "span", "name": "verify.quorum", "where": {"route": "mask"}, "stat": "median_ms"},
    }
    json.dump(metric, open(root / "benchmark/layer_metrics/sync_quorum_ms_per_drain.json", "w"))
    bench["configs"].append({"name": "ecdsa-7v", "source": "x", "file": "benchmark/configs/ecdsa-7v.json", "reduced": ["peers"], "why": "x"})
    bench["workloads"].append({"name": "ecdsa-7v.sync-short", "config": "ecdsa-7v", "traffic": "sync-short", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "sync_sigs_per_s":
            m["workloads"].append("ecdsa-7v.sync-short")
    for m in bench["per_layer"]:  # BENCHMARK.json alone says which cell reports what
        if m["name"] in ("sync_pack_ms_per_chunk", "sync_host_ms_per_call"):
            m["workloads"].append("ecdsa-7v.sync-short")
    bench["per_layer"].append(dict({k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")}, workloads=["ecdsa-7v.sync-short"]))
    json.dump(bench, open(root / "BENCHMARK.json", "w"))

    cell = bench_run.load_cell("ecdsa-7v.sync-short", str(root))
    assert cell["config"]["validators"] == 7 and cell["traffic"]["lanes_per_call"] == 2048
    names = [m["name"] for _p, m in cell["per_layer"]]
    assert names == ["compiles_in_window", "sync_host_ms_per_call", "sync_pack_ms_per_chunk", "sync_quorum_ms_per_drain", "warm_s"]
    assert "msgs_per_flush" not in names  # the floods' metric
    assert [m["name"] for _p, m in cell["end_to_end"]] == ["setup_s", "sync_sigs_per_s"]
    # ... and an old cell does not get the new cell's metric.
    old = bench_run.load_cell("ecdsa-4v.sync", str(root))
    assert "sync_quorum_ms_per_drain" not in [m["name"] for _p, m in old["per_layer"]]
    # The new metric reads spans that exist, with no code.
    from benchmark.lib.readers import read_metric

    path, m = next((p, m) for p, m in cell["per_layer"] if m["name"] == "sync_quorum_ms_per_drain")
    spans = [
        ("X", "verify.quorum", "t", 0, 3000, {"route": "mask"}),
        ("X", "verify.quorum", "t", 0, 1000, {"route": "mask"}),
        ("X", "verify.quorum", "t", 0, 9000, {"route": "host-int"}),
        ("X", "verify.pack", "t", 0, 7000, {"route": "mask"}),
    ]
    assert read_metric({"spans": spans, "counts": {}}, m, path) == pytest.approx(2.0)
    assert read_metric({"spans": [], "counts": {}}, m, path) is None  # nothing to read
    # No file that was there changed.
    for rel, body in before.items():
        assert open(root / rel, "rb").read() == body, rel


STUB_DRIVER = '''"""A driver the harness has never seen: no engine, no verifier, no jax."""
from types import SimpleNamespace

from benchmark.lib.harness import Probe


def setup(config, traffic, seed, seconds):
    return SimpleNamespace(probe=Probe(), info={"stub": config["validators"]}, seed=seed, n=traffic["operations"])


def warm(st):
    st.warmed = True


def measure(st, seconds):
    samples = [1.0 + (st.seed + i) % 7 for i in range(st.n)]
    for i in range(st.n):
        st.probe.boundary(float(i), st.n - i)
    return samples, {"attempted": st.n, "failed": 0, "operations": st.n, "elapsed_s": seconds}


def check(st):
    return st.warmed
'''


def test_a_cell_on_a_new_driver_reports_finalize_p50_ms_with_new_files_and_appended_entries_only(tmp_path, monkeypatch):
    """ISSUE 33: before it a metric file's ``drivers`` key dropped every
    end-to-end metric the benchmark has for a cell on a driver the harness
    had never seen (PR 26 met this)."""
    import importlib.util

    root = tmp_path / "copy"
    shutil.copytree(HERE, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {
        os.path.relpath(os.path.join(d, f), root): open(os.path.join(d, f), "rb").read()
        for d, _s, fs in os.walk(root) for f in fs
    }
    # New files: a driver, a mix that names it.  Appended entries: the cell,
    # and its name on the lists of the metrics it reports.
    (root / "benchmark/drivers/stub.py").write_text(STUB_DRIVER)
    json.dump({"name": "stub-mix", "driver": "stub", "operations": 21, "trace_seconds": 0.1},
              open(root / "benchmark/traffic/stub-mix.json", "w"))
    cell_name = "ecdsa-4v.stub-mix"
    bench["workloads"].append({"name": cell_name, "config": "ecdsa-4v", "traffic": "stub-mix", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] in ("finalize_p50_ms", "finalize_p90_ms"):
            m["workloads"].append(cell_name)
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    for rel, body in before.items():  # no file that was there changed
        assert open(root / rel, "rb").read() == body, rel

    cell = bench_run.load_cell(cell_name, str(root))
    assert [m["name"] for _p, m in cell["end_to_end"]] == ["finalize_p50_ms", "finalize_p90_ms", "setup_s"]
    assert [m["name"] for _p, m in cell["per_layer"]] == ["compiles_in_window", "warm_s"]

    # ... and the harness drives it: the copy's driver under the name the
    # mix gives, the copy's cell, the repository's run().
    spec = importlib.util.spec_from_file_location("benchmark.drivers.stub", root / "benchmark/drivers/stub.py")
    stub = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stub)
    monkeypatch.setitem(sys.modules, "benchmark.drivers.stub", stub)
    real = bench_run.load_cell
    monkeypatch.setattr(bench_run, "load_cell", lambda name: real(name, str(root)))
    monkeypatch.setattr(bench_run, "require_tpu", lambda chips: {"platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    out = io.StringIO()
    args = bench_run.argparse.Namespace(workload=cell_name, seed=3, seconds=1.0, trace=0)
    with contextlib.redirect_stdout(out):
        rc = bench_run.run(args)
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    result, info = lines[-1], lines[-2]
    assert rc == 0 and result["correct"] is True and result["attempted"] == 21
    assert set(result["metrics"]) == {"finalize_p50_ms", "finalize_p90_ms", "setup_s"}
    samples = sorted(1.0 + (3 + i) % 7 for i in range(21))
    assert result["metrics"]["finalize_p50_ms"] == {"value": samples[10], "unit": "ms"}
    assert info["driver"] == "stub" and info["stub"] == 4


def test_a_traced_flood_of_twelve_signed_heights_still_has_its_window(monkeypatch):
    """ISSUE 33: a flood that runs dry long before ``--seconds`` opens its
    profiler window by the heights left, gets two whole heights into it,
    and says that it ran out."""
    # 12 heights for the window: 5 s x 19.2 messages a second / 8 a height.
    rc, lines = _run(monkeypatch, "ecdsa-100v.flood", 1, 5.0, presign_msgs_per_s=19.2, trace_seconds=0.02)
    result, info = lines[-1], lines[-2]
    traced = next(line["trace"] for line in lines if "trace" in line)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert info["presigned_heights"] == 1 + 3 + 12 and result["attempted"] == 12
    assert info["ran_out_of_presigned"] is True and info["elapsed_s"] < 4.0
    assert traced["opened_by"] == "operations_left" and traced["operations"] >= 2
    assert {"busy_s", "window_s"} <= set(result["device"]) and result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
