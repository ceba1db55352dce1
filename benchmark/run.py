#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Load, warm up, measure for ``--seconds``, print one JSON object as the last
line of stdout, exit.  Everything about a cell is data found by name:
``BENCHMARK.json`` names the cell's configuration and traffic mix,
``benchmark/configs/<config>.json`` is the deployment,
``benchmark/traffic/<mix>.json`` the mix and the driver it needs,
``benchmark/drivers/<driver>.py`` the driver, and
``benchmark/end_to_end/*.json`` / ``benchmark/layer_metrics/*.json`` one
file per metric.  There is no CPU mode: without a TPU, or with fewer chips
than the cell asks for, the process exits non-zero in seconds and prints
no result.

Which cell reports which metric is ``BENCHMARK.json``'s alone to say: a
metric's entry there lists the cells under ``workloads`` (no list: every
cell).  A metric file says how the number is read, never where.

A driver is any module ``benchmark/drivers/<name>.py`` with four functions;
a new one needs no edit to a file that is there:

``setup(config, traffic, seed, seconds) -> st``  an object with ``st.probe``
    (the harness replaces it in a traced run) and ``st.info`` (a dict, printed
    on an earlier line); makes every input from ``seed``;
``warm(st)``  loads every shape the window will use; nothing may compile later;
``measure(st, seconds) -> (samples, counts)``  ``samples`` feed ``read`` kinds
    ``percentile`` and ``rate`` (``sum_samples``); ``counts`` has ``attempted``,
    ``failed``, ``operations``, ``elapsed_s`` and whatever the cell's metrics
    ``read`` by ``key`` (``live_lanes``, ``flush_sizes``, ``op_spans``); between
    two operations it calls ``st.probe.boundary(elapsed_s, left)`` and it goes
    on while ``st.probe.wants_more()``; where ``st.probe.leading()`` it may
    drive one dispatch in place of a whole operation, counted as none;
``check(st) -> bool``  holds what the window produced to the configuration's
    guarantees, against labels and a reference that are the driver's own: a
    driver that brings its own committee (BLS, stake-weighted power) brings
    the generator's labels and a host oracle for them beside it.  What it
    compared it leaves in ``st.info["compared"]`` as ``{name: {"value": n,
    "limit": m}}``; the harness prints it with its own three beside ``correct``.

``--trace 0`` runs with ``obs/trace``, the cost ledger and the profiler off
and reports the cell's end-to-end metrics.  ``--trace 1`` turns the span
ring and the cost ledger on for the whole window, captures a
``jax.profiler`` window of whole operations at the end of it, and reports the cell's
per-layer metrics, the device's busy time and a breakdown.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python gets

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_ACCELERATOR_RC = 3
NOT_A_CHECKOUT_RC = 4


def say(**line) -> None:
    """An earlier line of the output: context, never the result."""
    print(json.dumps(line), flush=True)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, mix and metric files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; there are: {sorted(cells)}")
    cell = cells[name]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, config_entry["file"]))
    here = os.path.join(root, "benchmark")
    traffic = load_json(os.path.join(here, "traffic", cell["traffic"] + ".json"))

    def metrics_of(group: str, directory: str) -> list:
        return metric_files(os.path.join(here, directory), bench[group], cell)

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": metrics_of("end_to_end", "end_to_end"),
        "per_layer": metrics_of("per_layer", "layer_metrics"),
    }


def metric_files(directory: str, declared: list, cell: dict) -> list:
    """``(path, metric)`` for every metric file of ``directory`` whose entry
    in ``BENCHMARK.json`` (``declared``) applies to this cell: the entry lists
    the cell under ``workloads``, or has no such list."""
    entries = {m["name"]: m for m in declared}
    out = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        metric = load_json(path)
        entry = entries.get(metric["name"])
        if entry is None:
            continue
        if "workloads" in entry and cell["name"] not in entry["workloads"]:
            continue
        out.append((path, metric))
    return out


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits where it is no TPU or too few."""
    import jax

    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if found["platform"] != "tpu" or found["count"] < chips:
        print(
            f"benchmark: this cell needs {chips} TPU chip(s); JAX found "
            f"platform {found['platform']!r}, {found['count']} device(s) of kind "
            f"{found['kind']!r}. There is no CPU mode.",
            file=sys.stderr,
            flush=True,
        )
        raise SystemExit(NO_ACCELERATOR_RC)
    return found


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(args) -> int:
    sys.path.insert(0, ROOT)
    try:
        import go_ibft_tpu  # noqa: F401 - the system under test
    except ImportError:
        print(
            "benchmark: the program (go_ibft_tpu) is not in this directory",
            file=sys.stderr,
        )
        return NOT_A_CHECKOUT_RC
    cell = load_cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    tracing = bool(args.trace)
    # Persist every program, however quickly it compiled, so that a cell's
    # second run finds all of them in the cache (utils/jaxcache.py reads it).
    os.environ.setdefault("GO_IBFT_CACHE_MIN_COMPILE_S", "0")

    device = require_tpu(cell["cell"]["chips"])

    from benchmark.lib import trace_reduce
    from benchmark.lib.harness import CompileEvents, TraceProbe, ledger_delta
    from benchmark.lib.readers import read_metric
    from benchmark.lib.stats import samples_beyond

    from go_ibft_tpu.obs import ledger as cost_ledger
    from go_ibft_tpu.obs import trace as obs_trace
    from go_ibft_tpu.utils import metrics as program_metrics

    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    events = CompileEvents()
    if tracing:
        obs_trace.enable(capacity=1 << 20)
        cost_ledger.enable()

    st = driver.setup(config, traffic, args.seed, args.seconds)
    t_warm = time.perf_counter()
    driver.warm(st)
    warm_s = time.perf_counter() - t_warm

    edges: dict = {}
    probe = None
    if tracing:
        trace_dir = os.path.join(ROOT, ".cache", "benchmark_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        probe = TraceProbe(
            trace_dir,
            args.seconds,
            min(float(traffic["trace_seconds"]), args.seconds),
            lambda edge: edges.__setitem__(edge, cost_ledger.snapshot()),
        )
        st.probe = probe
        # The program's own latency histograms (the engine's accept ->
        # finalize among them), for a cross-check on an earlier line.
        program_metrics.enable_fixed_histograms()

    ledger_before = cost_ledger.snapshot()
    compiles_before = events.requests
    window_t0 = time.perf_counter()
    setup_s = window_t0 - T_START
    samples, counts = driver.measure(st, args.seconds)
    if probe is not None:
        probe.stop()
    window_t1 = time.perf_counter()
    ledger_after = cost_ledger.snapshot()
    compiles_in_window = events.requests - compiles_before
    device["memory_peak_bytes"] = memory_peak_bytes()
    spans = None
    if tracing:
        lo, hi = window_t0 * 1e6, window_t1 * 1e6
        spans = [r for r in obs_trace.recorder().snapshot() if lo <= r[3] <= hi]
        obs_trace.disable()
        cost_ledger.disable()
        program_metrics.disable_fixed_histograms()
        say(
            program_histograms_mean_ms={
                "/".join(key): {"count": h["count"], "mean_ms": h["sum"] / h["count"]}
                for key, h in program_metrics.fixed_histograms_snapshot().items()
                if h["count"]
            }
        )

    checked = driver.check(st)
    counts.update(
        setup_s=setup_s, warm_s=warm_s, compiles_in_window=compiles_in_window
    )
    failed = counts["failed"]
    correct = bool(checked) and failed == 0 and compiles_in_window == 0
    # Every number ``correct`` rests on beside its limit (all exact: 0).
    compared = {
        "operations_failed": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "guarantees_broken": {"value": 0 if checked else 1, "limit": 0},
        **st.info.pop("compared", {}),
    }

    ctx = {
        "samples": samples,
        "counts": counts,
        "spans": spans,
        "ledger": ledger_delta(ledger_before, ledger_after) if tracing else None,
        "ledger_traced": (
            ledger_delta(edges.get("start"), edges.get("stop")) if tracing else None
        ),
        "trace": None,
        "config": config,
        "traffic": traffic,
        "device_kind": device["kind"],
    }
    breakdown = None
    if tracing:
        path = trace_reduce.newest_xplane(probe.trace_dir)
        if path is not None:
            reduced = trace_reduce.reduce(trace_reduce.load(path))
            ctx["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
            say(
                trace={
                    "xplane": os.path.relpath(path, ROOT),
                    "idle_share": reduced["idle_share"],
                    "events_dropped": reduced["dropped"],
                    "programs": reduced["programs"],
                    "single_ops": reduced["single_ops"],
                    "longest_gaps": reduced["longest_gaps"],
                    "xplane_bytes": os.path.getsize(path),
                    "opened_by": probe.opened_by,
                    "operations": probe.operations,
                    "stop_trace_s": probe.stop_trace_s,
                }
            )
        say(ledger=ctx["ledger"], ledger_traced=ctx["ledger_traced"])

    metrics = {}
    beyond = {}
    for path, metric in cell["per_layer" if tracing else "end_to_end"]:
        value = read_metric(ctx, metric, path)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        if metric["read"]["kind"] == "percentile":
            beyond[metric["name"]] = samples_beyond(len(samples), metric["read"]["q"])

    say(
        workload=args.workload,
        seed=args.seed,
        seconds=args.seconds,
        trace=args.trace,
        driver=traffic["driver"],
        operations=counts["operations"],
        elapsed_s=counts["elapsed_s"],
        ran_out_of_presigned=counts.get("ran_out_of_presigned", False),
        compile_events=events.snapshot(),
        checked=bool(checked),
        samples_beyond=beyond,  # a percentile wants ten samples beyond it
        **st.info,
    )
    result = {
        "correct": correct,
        "attempted": counts["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared  # last in the line, and the last lines of stderr
    print(json.dumps(result), flush=True)
    for name, pair in compared.items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})", file=sys.stderr)
    sys.stderr.flush()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
