"""Sharded mask verification over a multi-device mesh.

Runs on the 8 virtual CPU devices (conftest forces
``--xla_force_host_platform_device_count=8``); asserts the sharded result
equals the single-device result exactly — the determinism contract across
partitionings.
"""

import jax
import numpy as np
import pytest

from go_ibft_tpu.bench import build_round_workload
from go_ibft_tpu.parallel import make_mesh
from go_ibft_tpu.verify.batch import _recover_kernel
from go_ibft_tpu.verify.mesh_batch import mesh_verify_mask

# The shard_map mesh program is one of the largest compiles in the tree
# (tens of minutes cold on a CI runner); keep it out of the fast tier.
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def cpu8():
    devices = jax.devices("cpu")
    if len(devices) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    return devices[:8]


@pytest.mark.parametrize("vp", [1, 2])
def test_mesh_matches_single_device(cpu8, vp):
    w = build_round_workload(8, corrupt_frac=0.25, seed=5, pad_lanes=8)
    hz, r, s, v, signers, live = w.seals
    rows = (hz, r, s, v, signers, w.table, live)
    mesh = make_mesh(8, vp=vp, devices=cpu8)  # lanes over dp, replicated over vp
    got = np.asarray(mesh_verify_mask(mesh)(*rows))
    want = np.asarray(_recover_kernel(*rows))  # the single-device program
    assert np.array_equal(got, want)
    n = w.n_validators
    assert np.array_equal(got[:n], w.expected_seal_mask) and not got[n:].any()


def test_mesh_device_count_validation(cpu8):
    with pytest.raises(ValueError):
        make_mesh(8, vp=3, devices=cpu8)
