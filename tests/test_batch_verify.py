"""Host vs device BatchVerifier equivalence on adversarial batches.

The device mask must equal the host mask lane-for-lane on every corruption
mode — this is the determinism contract (SURVEY.md §7 (e)) that lets the
engine swap verifiers without changing observable consensus behavior.
"""

import numpy as np
import pytest

from go_ibft_tpu.crypto import PrivateKey, keccak256
from go_ibft_tpu.crypto.backend import ECDSABackend, encode_signature, proposal_hash_of
from go_ibft_tpu.crypto import ecdsa as ec
from go_ibft_tpu.messages import Proposal, View
from go_ibft_tpu.messages.helpers import CommittedSeal
from go_ibft_tpu.verify import DeviceBatchVerifier, HostBatchVerifier

# Cold EC-ladder kernel compiles take minutes; slow tier only.
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def cluster_keys():
    keys = [PrivateKey.from_seed(f"bv-{i}".encode()) for i in range(4)]
    powers = {k.address: 1 for k in keys}
    backends = [
        ECDSABackend(k, ECDSABackend.static_validators(powers)) for k in keys
    ]
    return keys, powers, backends


def _verifiers(powers):
    src = ECDSABackend.static_validators(powers)
    return HostBatchVerifier(src), DeviceBatchVerifier(src)


def test_verify_senders_masks_agree(cluster_keys):
    keys, powers, backends = cluster_keys
    view = View(height=5, round=0)
    msgs = [b.build_prepare_message(b"\x11" * 32, view) for b in backends]

    # corruption modes:
    msgs[1].signature = msgs[1].signature[:-1] + bytes(
        [msgs[1].signature[-1] ^ 1]
    )  # wrong recovery id -> recovers different key
    outsider = ECDSABackend(
        PrivateKey.from_seed(b"outsider"),
        ECDSABackend.static_validators(powers),
    )
    msgs.append(outsider.build_prepare_message(b"\x11" * 32, view))  # not a validator
    stolen = backends[2].build_prepare_message(b"\x22" * 32, view)
    stolen.sender = keys[3].address  # claimed sender != recovered signer
    msgs.append(stolen)
    tampered = backends[3].build_prepare_message(b"\x33" * 32, view)
    tampered.prepare_data.proposal_hash = b"\x44" * 32  # payload mutated post-sign
    msgs.append(tampered)

    host, device = _verifiers(powers)
    hm = host.verify_senders(msgs)
    dm = device.verify_senders(msgs)
    assert list(hm) == [True, False, True, True, False, False, False]
    assert np.array_equal(hm, dm)


def test_verify_senders_oversize_payload_host_digest(cluster_keys):
    """A payload above the largest keccak block bucket (e.g. a PREPREPARE
    whose proposal/RCC runs to several KB) must verify, not crash the
    packer: its digest is computed by the host keccak and injected into
    the device batch; the ladder still runs on device (r05 fix — a
    57-block PREPREPARE raised ValueError through ingress and stalled a
    live cluster)."""
    from go_ibft_tpu.verify.batch import DeviceBatchVerifier as DBV

    keys, powers, backends = cluster_keys
    view = View(height=5, round=0)
    big_raw = bytes(range(256)) * 30  # 7680B payload >> 32-block bucket max
    msgs = [b.build_prepare_message(b"\x11" * 32, view) for b in backends[:2]]
    msgs.append(backends[2].build_preprepare_message(big_raw, None, view))
    assert (
        len(msgs[-1].encode(include_signature=False)) > DBV._MAX_DEVICE_PAYLOAD
    )
    tampered = backends[3].build_preprepare_message(big_raw, None, view)
    tampered.preprepare_data.proposal.raw_proposal = big_raw[:-1] + b"\x00"
    msgs.append(tampered)  # oversize AND mutated post-sign -> must fail

    host, device = _verifiers(powers)
    hm = host.verify_senders(msgs)
    dm = device.verify_senders(msgs)
    assert list(hm) == [True, True, True, False]
    assert np.array_equal(hm, dm)


def test_verify_senders_mixed_heights(cluster_keys):
    keys, powers, backends = cluster_keys
    msgs = [
        backends[i].build_prepare_message(b"\x55" * 32, View(height=h, round=0))
        for i, h in [(0, 1), (1, 2), (2, 1)]
    ]
    host, device = _verifiers(powers)
    assert np.array_equal(host.verify_senders(msgs), device.verify_senders(msgs))
    assert list(host.verify_senders(msgs)) == [True, True, True]


def test_verify_committed_seals_masks_agree(cluster_keys):
    keys, powers, backends = cluster_keys
    proposal = Proposal(raw_proposal=b"the block", round=0)
    phash = proposal_hash_of(proposal)
    view = View(height=9, round=0)
    commits = [b.build_commit_message(phash, view) for b in backends]
    seals = [
        CommittedSeal(signer=m.sender, signature=m.commit_data.committed_seal)
        for m in commits
    ]
    # corruptions: seal signed over a different hash; signer mismatch;
    # garbage signature; non-validator signer
    wrong_hash = encode_signature(*ec.sign(keys[1], keccak256(b"other")))
    seals.append(CommittedSeal(signer=keys[1].address, signature=wrong_hash))
    seals.append(CommittedSeal(signer=keys[0].address, signature=seals[1].signature))
    seals.append(CommittedSeal(signer=keys[2].address, signature=b"\x01" * 65))
    out_key = PrivateKey.from_seed(b"seal-outsider")
    seals.append(
        CommittedSeal(
            signer=out_key.address,
            signature=encode_signature(*ec.sign(out_key, phash)),
        )
    )

    host, device = _verifiers(powers)
    hm = host.verify_committed_seals(phash, seals, height=9)
    dm = device.verify_committed_seals(phash, seals, height=9)
    assert list(hm) == [True] * 4 + [False] * 4
    assert np.array_equal(hm, dm)


def test_seal_semantics_host_backend_matches_batch_verifiers(cluster_keys):
    """Differential: ECDSABackend.is_valid_committed_seal (the engine's
    sequential path) must produce the SAME accept-set as both batch
    verifiers — including the validator-membership rule — over valid,
    tampered, and non-member seals (VERDICT r1 weak #5; reference seam
    core/backend.go:50-55)."""
    keys, powers, backends = cluster_keys
    proposal = Proposal(raw_proposal=b"diff block", round=1)
    phash = proposal_hash_of(proposal)
    view = View(height=3, round=1)
    commits = [b.build_commit_message(phash, view) for b in backends]
    seals = [
        CommittedSeal(signer=m.sender, signature=m.commit_data.committed_seal)
        for m in commits
    ]
    # tampered: signature over a different digest
    seals.append(
        CommittedSeal(
            signer=keys[0].address,
            signature=encode_signature(*ec.sign(keys[0], keccak256(b"evil"))),
        )
    )
    # non-member: valid signature from an outsider key
    out_key = PrivateKey.from_seed(b"diff-outsider")
    seals.append(
        CommittedSeal(
            signer=out_key.address,
            signature=encode_signature(*ec.sign(out_key, phash)),
        )
    )
    # signer-mismatch: member's signature claimed by another member
    seals.append(CommittedSeal(signer=keys[1].address, signature=seals[0].signature))

    host, device = _verifiers(powers)
    hm = host.verify_committed_seals(phash, seals, height=3)
    dm = device.verify_committed_seals(phash, seals, height=3)
    sm = [backends[0].is_valid_committed_seal(phash, s, 3) for s in seals]
    assert sm == [True] * 4 + [False] * 3
    assert list(hm) == sm
    assert np.array_equal(hm, dm)


def test_empty_batches(cluster_keys):
    _, powers, _ = cluster_keys
    host, device = _verifiers(powers)
    assert host.verify_senders([]).shape == (0,)
    assert device.verify_senders([]).shape == (0,)
    assert device.verify_committed_seals(b"\x00" * 32, [], height=0).shape == (0,)


def test_joint_commit_dispatch_matches_split_drains(cluster_keys):
    """A COMMIT flood through ``verify_senders`` (both phases, one dispatch:
    each seal beside its envelope) must leave the verdicts the split seal
    drain computes and the host oracle's masks, corrupted lanes included."""
    keys, powers, backends = cluster_keys
    view = View(height=9, round=0)
    phash = proposal_hash_of(Proposal(raw_proposal=b"round block", round=0))
    commits = [b.build_commit_message(phash, view) for b in backends]
    # corrupt one envelope and another message's seal
    commits[1].signature = commits[1].signature[:5] + bytes(
        [commits[1].signature[5] ^ 0xFF]
    ) + commits[1].signature[6:]
    seal = commits[2].commit_data.committed_seal
    commits[2].commit_data.committed_seal = seal[:5] + bytes([seal[5] ^ 0xFF]) + seal[6:]
    commits[2] = backends[2]._sign_envelope(commits[2])  # a good envelope over it
    seals = [
        CommittedSeal(signer=m.sender, signature=m.commit_data.committed_seal)
        for m in commits
    ]

    host, device = _verifiers(powers)
    _, split = _verifiers(powers)
    sm = device.verify_senders(commits)
    assert np.array_equal(sm, host.verify_senders(commits))
    want = host.verify_committed_seals(phash, seals, 9)
    assert device.cached_seal_verdicts(phash, seals, 9) == want.tolist()
    assert np.array_equal(device.verify_committed_seals(phash, seals, 9), want)
    assert np.array_equal(split.verify_committed_seals(phash, seals, 9), want)
    assert not want[2] and want.sum() == len(seals) - 1
