"""``BENCHMARK.json`` and the data files the harness finds by name: the
limits of the benchmark's contract that can be checked without a chip."""

import glob
import importlib
import inspect
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmark")
if HERE not in sys.path:
    sys.path.insert(0, HERE)  # for ``import run``: benchmark/run.py

import contract_predicates as contract  # noqa: E402  (beside this file)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def bench():
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def _files(sub):
    return {
        os.path.splitext(os.path.basename(p))[0]: _load(p)
        for p in glob.glob(os.path.join(HERE, sub, "*.json"))
    }


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["command"]) <= 32
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    # The command names no file of the repo outside paths.
    for word in bench["command"]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word == p or word.startswith(p + "/") for p in bench["paths"])
    # A full check of 24 cells at this length fits the driver's budget.
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_name_and_unit_uses_the_allowed_characters(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in bench[group]]
        assert len(seen) == len(set(seen)), f"a name twice in {group}"
        names += seen
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for w in bench["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in bench["configs"]:
        names += c["reduced"]
        assert len(c["reduced"]) <= 16
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for root, _dirs, files in os.walk(HERE):
        if "__pycache__" in root:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), ROOT)
            assert PATH.match(rel), rel


def test_entries_have_just_the_keys_of_the_contract(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    for text in (
        [c["source"] for c in bench["configs"]]
        + [c["why"] for c in bench["configs"]]
        + [w["why"] for w in bench["workloads"]]
        + [m["layer"] for m in bench["per_layer"]]
        + bench["command"]
    ):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, text


def test_cells_configs_and_mixes_are_found_by_name(bench):
    assert 2 <= len(bench["workloads"]) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        body = _load(os.path.join(ROOT, c["file"]))
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert body["quorum"] == 2 * body["validators"] // 3 + 1
        assert {"guarantees", "assumed", "source", "scheme"} <= set(body)
    for w in bench["workloads"]:
        mix = _load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert mix["name"] == w["traffic"]
        assert os.path.isfile(os.path.join(HERE, "drivers", mix["driver"] + ".py"))


def test_setup_s_is_there_with_its_bound(bench):
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25 and "workloads" not in setup[0]


def test_metric_files_and_benchmark_json_agree(bench):
    for group, sub in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        files = _files(sub)
        declared = {m["name"]: m for m in bench[group]}
        assert set(files) == set(declared), (sub, set(files) ^ set(declared))
        for name, f in files.items():
            assert f["name"] == name
            for key in ("unit", "better", "source") + (("layer", "moves") if group == "per_layer" else ()):
                assert f[key] == declared[name][key], (name, key)
            assert f["read"]["kind"] in ("span", "ledger", "count", "percentile", "rate", "reader")
            # Which cell reports it is BENCHMARK.json's alone to say (PR 33): a
            # list in the file would decide nothing and go stale.
            assert "drivers" not in f and "workloads" not in f, name
            if f["read"]["kind"] == "reader":
                module = f["read"].get("module", name)
                assert os.path.isfile(os.path.join(HERE, sub, module + ".py")), name


def _cells_of(metric, bench):
    import run as bench_run  # benchmark/run.py

    out = []
    for w in bench["workloads"]:
        directory = os.path.join(
            HERE, "layer_metrics" if "layer" in metric else "end_to_end"
        )
        got = bench_run.metric_files(directory, [metric], w)
        if got:
            out.append(w["name"])
    return out


def test_every_layer_metric_names_an_end_to_end_metric_of_a_cell_it_applies_to(bench):
    sys.path.insert(0, HERE)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    reported = {w["name"]: {"e2e": set(), "layer": set()} for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        cells = _cells_of(m, bench)
        assert cells, m["name"]
        assert sorted(cells) == sorted(m.get("workloads", [w["name"] for w in bench["workloads"]]))
        for c in cells:
            reported[c]["e2e"].add(m["name"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        cells = _cells_of(m, bench)
        assert cells, f"{m['name']} applies to no cell"
        assert sorted(cells) == sorted(m.get("workloads", [w["name"] for w in bench["workloads"]]))
        for c in cells:
            # A per-layer metric is reported only where the metric it moves is.
            assert m["moves"] in reported[c]["e2e"], (m["name"], c)
            reported[c]["layer"].add(m["name"])
    for cell, got in reported.items():
        assert "setup_s" in got["e2e"] and len(got["e2e"]) >= 2, cell
        assert got["layer"], cell


@pytest.mark.parametrize("cell", sorted(contract.SELECTION_OF_PR_32))
def test_the_four_cells_select_the_metrics_they_selected_before_pr_33(cell):
    """In order, every metric of ``contract_predicates.SELECTION_OF_PR_32``:
    an ordered subset, so a later PR may give an accepted cell one more."""
    contract.pr_32_selection(cell)


CELL_TEST_FILES = sorted(glob.glob(os.path.join(os.path.dirname(os.path.abspath(__file__)), "test_cell_*.py")))


def _cell_test_module(path):
    """A cell's own test file, as pytest imported it (this directory is on
    ``sys.path``: the files import each other by bare name)."""
    return importlib.import_module(os.path.splitext(os.path.basename(path))[0])


@pytest.mark.parametrize("path", CELL_TEST_FILES, ids=os.path.basename)
def test_every_cells_own_test_file_says_what_it_holds_of_benchmark_json_through_declared(path):
    """``declared(root=ROOT)``: all the file asserts of ``BENCHMARK.json``, so
    that the copy test below can hold it to a tree with a further cell in it.
    A later ``model_config`` PR's new ``test_cell_*.py`` is found by the glob."""
    fn = getattr(_cell_test_module(path), "declared", None)
    assert callable(fn), f"{os.path.basename(path)} has no declared(root)"
    root = inspect.signature(fn).parameters.get("root")
    assert root is not None and root.default == ROOT
    fn()  # and it holds of this checkout


# A further cell on either side, as a later ``model_config`` PR would bring it:
# the mix it runs, the lists it joins, the metric file its own per-layer entry
# copies, the accepted cell whose entries ``put_before`` puts it in front of
# (by configuration, cell and first per-layer entry), and what it must select.
FURTHER = {
    "flood": {
        "traffic": "flood",
        "joins": ["finalize_p50_ms", "finalize_p90_ms", "flushes_per_height"],
        "own": ("node_flushes_per_height", "again_flushes_per_height"),
        "before": ("ecdsa-100v-node", contract.NODE, "node_msgs_per_flush"),
        "selects": {
            "end_to_end": ["finalize_p50_ms", "finalize_p90_ms", "setup_s"],
            "per_layer": ["again_flushes_per_height", "compiles_in_window", "flushes_per_height", "warm_s"],
        },
    },
    "sync": {
        "traffic": "sync",
        "joins": ["sync_sigs_per_s"] + contract.JOINED_BY_PR_45,
        "own": ("sync_host_ms_per_call", "again_host_ms_per_call"),
        "before": ("ecdsa-100v-pos", contract.POS_SYNC, "pos_drains_per_call"),
        "selects": {
            "end_to_end": ["setup_s", "sync_sigs_per_s"],
            "per_layer": sorted(
                ["again_host_ms_per_call", "compiles_in_window", "warm_s"] + contract.JOINED_BY_PR_45
            ),
        },
    },
}


@pytest.mark.parametrize("where", ["appended_at_the_end", "put_before_an_accepted_cells"])
@pytest.mark.parametrize("side", sorted(FURTHER))
def test_a_further_cell_breaks_no_accepted_cells_contract(tmp_path, side, where):
    """What a later ``model_config`` PR does, on a copy (PR 43; both sides
    since PR 50): a configuration (``ecdsa-100v`` under a second name), a cell
    on the mix ``flood`` or ``sync``, a per-layer entry of its own, its name on
    the lists of the end-to-end metrics and shared readers of its side (``sync``:
    ``sync_sigs_per_s``, ``ecdsa_recover_roofline`` and every ``sync_*`` reader).
    Appended at the END of every list, as the benchmark's check wants new
    entries, or put immediately before an accepted cell's (the node's as ISSUE
    42 first asked; PR 45's): every accepted cell's contract holds either way,
    what every ``test_cell_*.py`` declares holds, and each accepted cell
    selects exactly what it selects in the checkout."""
    spec = FURTHER[side]
    root = tmp_path / "copy"
    shutil.copytree(HERE, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    bench = contract.bench_of()
    cfg = _load(os.path.join(HERE, "configs", "ecdsa-100v.json"))
    cfg["name"] = "ecdsa-100v-again"
    with open(root / "benchmark/configs/ecdsa-100v-again.json", "w") as fh:
        json.dump(cfg, fh)
    copied, own_name = spec["own"]
    metric = _load(os.path.join(HERE, "layer_metrics", copied + ".json"))
    metric["name"] = own_name
    with open(root / f"benchmark/layer_metrics/{own_name}.json", "w") as fh:
        json.dump(metric, fh)
    further = f"ecdsa-100v-again.{spec['traffic']}"
    before_config, before_cell, before_metric = spec["before"]

    def add(entries: list, entry, before) -> None:
        """``entry`` at the end, or before the first entry ``before`` names."""
        names = [e["name"] if isinstance(e, dict) else e for e in entries]
        at = len(entries) if where == "appended_at_the_end" or before not in names else names.index(before)
        entries.insert(at, entry)

    config_entry = dict(bench["configs"][0], name=cfg["name"], file="benchmark/configs/ecdsa-100v-again.json")
    add(bench["configs"], config_entry, before_config)
    cell = {"name": further, "config": cfg["name"], "traffic": spec["traffic"], "chips": 1, "why": "x"}
    add(bench["workloads"], cell, before_cell)
    own = {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    add(bench["per_layer"], dict(own, workloads=[further]), before_metric)
    listed = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in spec["joins"]:
        # ``flushes_per_height`` never listed the node: there the end is the only place.
        add(listed[name]["workloads"], further, before_cell)
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)

    contract.every_accepted_cell(str(root))
    for path in CELL_TEST_FILES:
        _cell_test_module(path).declared(str(root))
    for w in contract.bench_of()["workloads"]:
        assert contract.selection(w["name"], str(root)) == contract.selection(w["name"]), w["name"]
    assert contract.selection(further, str(root)) == spec["selects"]
    # ... and the predicates can fail: one of the node's metrics handed to the
    # further cell, or one of the PoS cell's handed to ``ecdsa-100v.sync``, is refused.
    for name, to, predicate in (
        ("node_msgs_per_flush", further, contract.node),
        ("pos_drains_per_call", "ecdsa-100v.sync", contract.pos_sync),
    ):
        broken = json.loads(json.dumps(bench))
        next(m for m in broken["per_layer"] if m["name"] == name)["workloads"].append(to)
        with open(root / "BENCHMARK.json", "w") as fh:
            json.dump(broken, fh)
        with pytest.raises(AssertionError):
            predicate(str(root))


def test_peaks_table_is_keyed_by_device_kind_and_refuses_the_unknown():
    from benchmark.lib import kernel_cost

    v5e = kernel_cost.peaks_of("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flop_per_s"] == 197e12
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        kernel_cost.peaks_of("TPU v9")
    # 2048 lanes against a 128-row table: 218 bytes a lane and the table.
    assert kernel_cost.ecdsa_recover_bytes(2048, 128) == 2048 * 218 + 128 * 20
    assert kernel_cost.module_prefixes("ecdsa_recover") == ["jit__recover_fn"]
