"""The chip bring-up contract, as far as a CPU can hold it (ISSUE 21).

``chip_smoke.py`` itself has no CPU mode — these pin what must be true
around it: it fails fast and loudly without a TPU, its parent never
initializes a JAX backend, the compile cache follows one rule, no mesh or
fleet child silently lands on the CPU, and the node warms what its
committee will dispatch.  A ``slow`` test runs the smoke's ``engine`` phase
function at 24 validators (32-lane bucket: the device route is really
taken) with the same assertions the chip run makes.
"""

import asyncio
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _run(code_or_args, *, env=None, cwd=REPO, timeout=120):
    args = (
        [sys.executable, "-c", code_or_args]
        if isinstance(code_or_args, str)
        else [sys.executable, *code_or_args]
    )
    return subprocess.run(
        args, cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout
    )


# ---------------------------------------------------------------------------
# chip_smoke.py: no accelerator, no result
# ---------------------------------------------------------------------------


def test_chip_smoke_without_accelerator_fails_fast_naming_the_platform():
    t0 = time.monotonic()
    proc = _run(["chip_smoke.py"], env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "'cpu'" in proc.stderr and "no accelerator" in proc.stderr
    assert '"ok"' not in proc.stdout  # no phase line, no result line


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run(["chip_smoke.py"], env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_chip_smoke_parent_initializes_no_backend():
    """Everything the parent does itself — keys, signed traffic, the node
    config, the transport and proof-verifier imports — with no backend
    initialized at the end (a parent that touched JAX would hold the chip
    its children need)."""
    code = """
import chip_smoke
from jax._src import xla_bridge

committee = chip_smoke.Committee(8, 0, 2)
traffic = committee.traffic(1, 2)
assert len(traffic["prepares"]) == 6 and len(traffic["commits"]) == 7
from go_ibft_tpu.net import GrpcTransport
from go_ibft_tpu.node.config import NodeConfig
from go_ibft_tpu.serve import FinalityProof, ProofVerifier
from go_ibft_tpu.sim.fleet import _http_get
from go_ibft_tpu.verify import HostBatchVerifier

mask = HostBatchVerifier(committee.src).verify_senders(traffic["prepares"])
assert int(mask.sum()) == 4, mask  # 2 of 6 corrupted
ProofVerifier().close()
assert not xla_bridge.backends_are_initialized()
print("NO-BACKEND")
"""
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NO-BACKEND" in proc.stdout


def test_committee_traffic_is_seeded_and_node_never_proposes():
    import chip_smoke

    a = chip_smoke.Committee(12, 3, 5)
    b = chip_smoke.Committee(12, 3, 5)
    assert a.node == b.node and a.node not in set(a.proposer.values())
    ta, tb = a.traffic(2, 4), b.traffic(2, 4)
    assert [m.encode() for m in ta["commits"]] == [m.encode() for m in tb["commits"]]
    assert len(ta["bad"]) == 4
    assert a.proposer[2] not in ta["bad"]  # the proposal stays valid


# ---------------------------------------------------------------------------
# one compile-cache rule
# ---------------------------------------------------------------------------

# The cache-directory variable this repo used to read; spelled in pieces so
# that a grep for it over the tree finds nothing left to retire.
_RETIRED_KNOB = "GO_IBFT_" + "CACHE_DIR"

_CACHE_PROBE = """
import json, os
import jax
from go_ibft_tpu.utils import jaxcache
print(json.dumps({
    "resolved": jaxcache.resolve_cache_dir(),
    "enabled": jaxcache.enable_persistent_cache() if os.environ.get("DO_ENABLE") else None,
    "config": jax.config.jax_compilation_cache_dir,
}))
"""


def test_cache_dir_from_outside_is_used_as_is_and_never_pruned(tmp_path):
    placed = tmp_path / "placed"
    placed.mkdir()
    stale = placed / "jit_old-cache"
    stale.write_bytes(b"x" * 64)
    ancient = time.time() - 10 * 365 * 24 * 3600
    os.utime(stale, (ancient, ancient))
    decoy = tmp_path / "decoy"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(placed),
        GO_IBFT_CACHE_TTL_S="1",  # would evict `stale` from a dir of ours
        DO_ENABLE="1",
    )
    env[_RETIRED_KNOB] = str(decoy)  # ignored
    proc = _run(_CACHE_PROBE, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {
        "resolved": str(placed),
        "enabled": str(placed),
        "config": str(placed),
    }
    assert stale.exists(), "a cache directory placed from outside was pruned"
    assert not decoy.exists()


def test_cache_dir_defaults_to_the_checkout(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env[_RETIRED_KNOB] = str(tmp_path / "d")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = _run(_CACHE_PROBE, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["resolved"] == str(REPO / ".cache" / "xla")
    assert not (tmp_path / "d").exists()


def test_boot_children_receive_the_cache_dir(tmp_path, monkeypatch):
    from go_ibft_tpu.boot import restart

    seen = {}

    def fake_run(cmd, **kw):
        seen["env"] = kw["env"]

        class _Proc:
            returncode = 0
            stdout = '{"cold": 0}\n'
            stderr = ""

        return _Proc()

    monkeypatch.setattr(restart.subprocess, "run", fake_run)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    cache_dir = str(tmp_path / "xla")
    restart.run_boot_leg(
        "cold", "digest_words_8l", cache_dir, str(tmp_path / "l.jsonl"), timeout_s=5
    )
    assert seen["env"]["JAX_COMPILATION_CACHE_DIR"] == cache_dir
    assert _RETIRED_KNOB not in seen["env"]


# ---------------------------------------------------------------------------
# no hidden CPU
# ---------------------------------------------------------------------------


def test_short_default_platform_never_becomes_a_cpu_mesh(monkeypatch):
    """One default-platform device and a request for four: ``make_mesh``
    raises and ``mesh_context`` returns None — neither may reach for the
    host CPU devices."""
    import jax

    from go_ibft_tpu.parallel import mesh as mesh_mod

    real = jax.devices

    def one_default_device(backend=None):
        assert backend is None, f"reached for the {backend!r} platform"
        return real()[:1]

    monkeypatch.setattr(mesh_mod.jax, "devices", one_default_device)
    assert mesh_mod.mesh_context(4) is None
    with pytest.raises(ValueError, match="need 4 devices"):
        mesh_mod.make_mesh(4)
    # explicit devices are the caller's choice and still work
    assert mesh_mod.make_mesh(2, devices=real()[:2]).devices.size == 2


def test_pallas_switch_raises_where_mosaic_cannot_compile(monkeypatch):
    from go_ibft_tpu.ops import keccak as keccak_mod

    monkeypatch.setenv("GO_IBFT_PALLAS", "1")
    with pytest.raises(RuntimeError, match="GO_IBFT_PALLAS=1"):
        keccak_mod._pallas_mode()
    monkeypatch.setenv("GO_IBFT_PALLAS", "interpret")
    assert keccak_mod._pallas_mode() == "interpret"


def test_fleet_pins_only_host_route_children_to_the_cpu(tmp_path, monkeypatch):
    from go_ibft_tpu.node.config import NodeConfig
    from go_ibft_tpu.sim import fleet

    paths = []
    for i, route in enumerate(("host", "device")):
        cfg = NodeConfig(
            node_id=i,
            key_seed=f"k{i}",
            data_dir=str(tmp_path / f"d{i}"),
            validators={"ab" * 20: 1},
            sched_route=route,
        )
        path = tmp_path / f"node{i}.toml"
        path.write_text(cfg.to_toml())
        paths.append(str(path))
    envs = []

    class _Popen:
        def __init__(self, cmd, **kw):
            envs.append(kw["env"])

    monkeypatch.setattr(fleet.subprocess, "Popen", _Popen)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    fleet.launch_fleet(paths, str(tmp_path))
    assert envs[0]["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in envs[1]  # inherits the environment untouched


# ---------------------------------------------------------------------------
# the node warms what its committee dispatches; flushes are counted where
# they run
# ---------------------------------------------------------------------------


def _warmed_by(monkeypatch):
    from go_ibft_tpu.sched.dispatch import CoalescedDispatcher

    warmed = []
    monkeypatch.setattr(
        CoalescedDispatcher,
        "warmup",
        lambda self, lanes=(8,), table_rows=8: warmed.append((tuple(lanes), table_rows)),
    )
    return CoalescedDispatcher, warmed


ALL_SIX = [((8,), 8), ((32,), 128), ((128,), 128), ((512,), 128), ((1024,), 128), ((2048,), 128)]


@pytest.mark.parametrize(
    "route, validators, read_tier, want",
    [
        # A phase of 100 validators is at most 128 lanes of a kind: three
        # ladder programs on the forced device route, and under "auto" none
        # that only a flush below the 16-lane cutover would pick (PR 39).
        ("device", 100, False, ALL_SIX[:3]),
        ("auto", 100, False, ALL_SIX[1:3]),
        ("auto", 300, False, [((32,), 128), ((128,), 128), ((512,), 512)]),
        # The proof API's read tier coalesces up to a full dispatch: all six.
        ("device", 100, True, ALL_SIX),
        ("auto", 100, True, ALL_SIX),
        ("device", 4, False, [((8,), 8)]),
        ("auto", 4, False, [((8,), 8)]),
    ],
)
def test_warmup_committee_derives_buckets_from_the_validator_count(
    monkeypatch, route, validators, read_tier, want
):
    cls, warmed = _warmed_by(monkeypatch)
    dispatcher = cls(route=route)
    dispatcher.warmup_committee(validators, read_tier=read_tier)
    assert warmed == want
    # ... and launches nothing else from then on: a flush of any size goes
    # out in chunks of the widest width loaded, each at the narrowest that
    # holds it, with the table rows that width was loaded with.
    assert dispatcher._rungs == tuple(lanes[0] for lanes, _rows in want)
    assert dispatcher._pad_lanes(1) == want[0][0][0]
    assert dispatcher._pad_lanes(want[-1][0][0]) == want[-1][0][0]
    table = dispatcher._table([b"\x01" * 20], want[-1][0][0])
    assert table.shape[0] == want[-1][1] and (table == table[0]).all()  # row 0 again


def test_dispatcher_counts_what_served_each_flush():
    import chip_smoke
    from go_ibft_tpu.sched import TenantScheduler

    committee = chip_smoke.Committee(8, 1, 1)
    traffic = committee.traffic(1, 2)
    with TenantScheduler(route="host") as sched:
        handle = sched.register("t", committee.src)
        mask = handle.verify_senders(traffic["prepares"])
        assert int(mask.sum()) == 4
        assert sched.stats()["served"] == {"host": 1}


async def test_ingress_coalesces_arrivals_a_slow_flush_held_back():
    """A flush that blocks the loop far past the window holds arrivals
    back in the transport; the messages that then trickle in one per loop
    turn must land in ONE batch, not one flush each."""
    from go_ibft_tpu.core.transport import BatchingIngress

    batches = []

    def slow_add(batch):
        batches.append(len(batch))
        time.sleep(0.02)  # a device dispatch: 10 windows

    ingress = BatchingIngress(slow_add, max_delay=0.002, eager_cutover=64)
    ingress.submit("first")
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert batches == [1]
    for i in range(6):
        ingress.submit(f"m{i}")
        await asyncio.sleep(0)  # one message per loop turn
    await asyncio.sleep(0.05)
    assert batches == [1, 6]
    ingress.close()


async def test_ingress_stays_eager_after_a_fast_flush():
    from go_ibft_tpu.core.transport import BatchingIngress

    batches = []
    ingress = BatchingIngress(
        lambda b: batches.append(len(b)), max_delay=0.002, eager_cutover=64
    )
    for i in range(3):
        ingress.submit(f"m{i}")
        await asyncio.sleep(0)
        await asyncio.sleep(0)
    assert batches == [1, 1, 1]
    ingress.close()


# ---------------------------------------------------------------------------
# the engine phase, on the CPU at a size that still takes the device route
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_phase_at_24_validators_takes_the_device_route():
    import chip_smoke

    line = chip_smoke.engine_phase(validators=24, heights=2, corrupt=4, seed=5)
    assert line["heights_finalized_round0"] == 2
    assert line["cutover"] == 16
    ledger = line["ledger"]
    # 2 ingress floods a height, each at twice the 32-lane rung; the seal
    # drain is served from the COMMIT flood's verdicts (ISSUE 32).
    assert ledger["device_dispatches"] == 4
    assert ledger["device_padded_lanes"] == 64 * ledger["device_dispatches"]
    assert line["compiles_after_warmup"] == 0
    assert all(v == 0 for v in line["counters"].values())
