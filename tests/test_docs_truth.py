"""What the documents, the Makefile and the workflows name, exists.

The failure this holds: a README that documents a script that was deleted,
a ``make`` target whose recipe runs a file that is gone, an environment
variable the deployment guide describes and nothing reads.  ``PERF.md``,
``ROADMAP.md`` and ``CHANGES.md`` name deleted files as history and are not
read here.
"""

import functools
import importlib.util
import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

DOCS = ["README.md"] + sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md")
)

# A word has the form of a repo path when it starts at one of the tree's
# directories, or is a bare ``*.py`` / ``*.json`` name: a file at the root,
# or one in the directory the sentence is about (looked up by its name).
_PATH = re.compile(
    r"^(?:(?:go_ibft_tpu|scripts|benchmark|tests|docs|examples)/[\w./-]+"
    r"|[\w-]+\.(?:py|json))$"
)

# Files a command writes, named in a document beside that command.
WRITTEN_BY = {
    "soak.json": "python scripts/chaos_replay.py --seed 7 --trace soak.json",
    "node0.json": 'runner.export_trace("node0.json")',
    "node1.json": 'runner.export_trace("node1.json")',
}


def _candidates(text: str):
    """Every word inside backticks (inline or fenced), trimmed of the
    punctuation, ``:line`` and ``::name`` suffixes prose hangs on a path."""
    for quoted in re.findall(r"```.*?```|`[^`\n]+`", text, flags=re.S):
        for word in quoted.strip("`").split():
            word = word.strip("\"'(),;")
            word = re.sub(r"(::[\w.]+|:\d+(-\d+)?|#\S*)$", "", word)
            word = word.rstrip(".:")
            if word.startswith("./"):
                word = word[2:]
            if _PATH.match(word):
                yield word


_SKIP_DIRS = {"chip_tmp", "chiprun_out", "__pycache__"}


@functools.cache
def _file_names() -> frozenset:
    """Every file name in the tree, hidden and scratch directories pruned."""
    names = set()
    for _root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d[0] != "." and d not in _SKIP_DIRS]
        names.update(files)
    return frozenset(names)


def _exists(word: str) -> bool:
    if "/" in word:
        return (REPO / word).exists()
    return word in _file_names()


def _missing(text: str):
    return sorted(
        {w for w in _candidates(text) if w not in WRITTEN_BY and not _exists(w)}
    )


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_and_make_target_a_document_names_exists(doc):
    text = (REPO / doc).read_text()
    assert _missing(text) == []
    quoted = " ".join(re.findall(r"```.*?```|`[^`\n]+`", text, flags=re.S))
    named = set(re.findall(r"\bmake\s+([a-z][a-z0-9-]*)", quoted))
    assert sorted(named - set(TARGETS)) == []


# ---------------------------------------------------------------------------
# Makefile and workflows
# ---------------------------------------------------------------------------


def _makefile_targets() -> dict:
    """target -> its recipe lines."""
    targets: dict = {}
    current = None
    for line in (REPO / "Makefile").read_text().splitlines():
        head = re.match(r"^([A-Za-z0-9_-]+):(?!=)", line)
        if head:
            current = head.group(1)
            targets[current] = []
        elif line.startswith("\t") and current is not None:
            targets[current].append(line.strip())
        elif line.strip() and not line.startswith("#") and not line.endswith("\\"):
            if not re.match(r"^\s", line):
                current = None
    return targets


TARGETS = _makefile_targets()


@pytest.mark.parametrize("target", sorted(TARGETS))
def test_every_program_a_make_target_runs_exists(target):
    recipe = " ".join(TARGETS[target])
    for path in re.findall(r"python3?\s+((?!-)\S+\.py)\b", recipe):
        assert (REPO / path).is_file(), (target, path)
    for module in re.findall(r"python3?\s+-m\s+([\w.]+)", recipe):
        assert importlib.util.find_spec(module) is not None, (target, module)
    for dep in re.findall(r"\$\(MAKE\)\s+([A-Za-z0-9_-]+)", recipe):
        assert dep in TARGETS, (target, dep)


def test_every_make_target_a_workflow_runs_exists():
    ran = {}
    for workflow in sorted((REPO / ".github" / "workflows").glob("*.yml")):
        for target in re.findall(
            r"\bmake\s+([A-Za-z0-9_-]+)", workflow.read_text()
        ):
            ran.setdefault(target, workflow.name)
    assert ran, "no workflow runs make"
    assert {t: w for t, w in ran.items() if t not in TARGETS} == {}


# ---------------------------------------------------------------------------
# Environment variables
# ---------------------------------------------------------------------------

_ENV = re.compile(r"GO_IBFT_[A-Z0-9_]+")


def _names_in(paths) -> set:
    out = set()
    for path in paths:
        out |= set(_ENV.findall(path.read_text()))
    return out


def test_environment_variables_documented_are_the_ones_read():
    """Every ``GO_IBFT_*`` the program, its scripts or the benchmark read is
    in the deployment or the observability guide; and neither guide names
    one that nothing in the repo reads."""
    read = _names_in(
        p
        for top in ("go_ibft_tpu", "scripts", "benchmark")
        for p in (REPO / top).rglob("*.py")
    )
    documented = _names_in(
        [REPO / "docs" / "DEPLOYMENT.md", REPO / "docs" / "OBSERVABILITY.md"]
    )
    assert sorted(read - documented) == []
    read_anywhere = read | _names_in(
        [*(REPO / "tests").rglob("*.py"), *REPO.glob("*.py"), REPO / "Makefile"]
    )
    assert sorted(documented - read_anywhere) == []


# ---------------------------------------------------------------------------
# One verifier surface: masks from the device, quorum from host integers
# ---------------------------------------------------------------------------


def _exported_verifiers() -> list:
    """Every class a subpackage exports that has the ``BatchVerifier``
    surface (``verify_senders``), the protocol itself included."""
    import importlib
    import inspect

    found = {}
    for sub in ("core", "verify", "chaos", "sched", "crypto", "net", "serve"):
        module = importlib.import_module(f"go_ibft_tpu.{sub}")
        for name in getattr(module, "__all__", ()):
            obj = getattr(module, name)
            if inspect.isclass(obj) and hasattr(obj, "verify_senders"):
                found[f"{sub}.{name}"] = obj
    return sorted(found.items())


VERIFIERS = _exported_verifiers()


def test_the_exported_verifiers_are_found():
    names = {name for name, _ in VERIFIERS}
    assert {
        "core.BatchVerifier",
        "verify.HostBatchVerifier",
        "verify.DeviceBatchVerifier",
        "verify.MeshBatchVerifier",
        "verify.ResilientBatchVerifier",
        "verify.AdaptiveBatchVerifier",
        "verify.EngineScope",
        "chaos.ChaoticVerifier",
    } <= names


@pytest.mark.parametrize("name, cls", VERIFIERS, ids=[n for n, _ in VERIFIERS])
def test_no_exported_verifier_has_a_fused_certify_surface(name, cls):
    """A verifier returns masks.  The fused mask-and-quorum family
    (``certify_*``, ``supports_fused``) could not hold one real stake (31
    bits) and went in PR 49; nothing grows it back on a class by forwarding."""
    grown = [
        attr
        for attr in dir(cls)
        if attr.startswith("certify_") or attr == "supports_fused"
    ]
    assert grown == []


def test_the_program_registry_is_the_compile_budgets_rows():
    """``docs/compile_budget.json`` pins exactly the programs the boot
    registry can build, in the registry's order: a family deleted from one
    and left in the other is a row nothing lowers, or a program no ratchet
    holds."""
    import json

    from go_ibft_tpu.boot.registry import program_registry

    budget = json.loads((REPO / "docs" / "compile_budget.json").read_text())
    rows = [name for name in budget if not name.startswith("_")]
    assert list(program_registry()) == rows
    assert len(rows) == 11 and "ecdsa_recover_8l" in rows and "digest_words_8l" in rows


_GONE = re.compile(
    r"certify_(senders|seals|round)|quorum_certify|round_certify|supports_fused"
    r"|verify_round_chunked|FusedBatchVerifier|power_reduce|split_power|profile_decompose"
)


def test_no_document_draws_the_fused_certify_family():
    drawn = {
        doc: sorted({m.group(0) for m in _GONE.finditer((REPO / doc).read_text())})
        for doc in DOCS + [".claude/skills/verify/SKILL.md"]
    }
    assert {doc: names for doc, names in drawn.items() if names} == {}
