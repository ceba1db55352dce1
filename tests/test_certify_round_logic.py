"""certify_round host-side logic with STUBBED device kernels (fast tier).

The heavy differential test (real ladders) lives in test_batch_verify.py's
slow tier; here the kernel is replaced so the pack/scatter/split/fallback
logic gets coverage on every fast run: malformed-lane filtering, output
index mapping, the split-at-half contract, and degenerate-round fallbacks.
"""

import numpy as np

from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
from go_ibft_tpu.messages.helpers import CommittedSeal
from go_ibft_tpu.messages.wire import Proposal, View
from go_ibft_tpu.verify import DeviceBatchVerifier
from go_ibft_tpu.verify import batch as batch_mod


def _fixture(n=4, height=3):
    keys = [PrivateKey.from_seed(b"crl-%d" % i) for i in range(n)]
    powers = {k.address: 1 for k in keys}
    src = ECDSABackend.static_validators(powers)
    backends = [ECDSABackend(k, src) for k in keys]
    view = View(height=height, round=0)
    phash = proposal_hash_of(Proposal(raw_proposal=b"logic block", round=0))
    msgs = [b.build_prepare_message(phash, view) for b in backends]
    seals = []
    for b in backends:
        commit = b.build_commit_message(phash, view)
        seals.append(
            CommittedSeal(
                signer=commit.sender,
                signature=commit.commit_data.committed_seal,
            )
        )
    return DeviceBatchVerifier(src), msgs, phash, seals


def _stub_kernels(monkeypatch, mask_fn):
    """Replace the device programs: digest -> zeros, round kernel -> mask_fn."""

    def fake_digest(blocks, counts, hz=None):
        zw = np.zeros((np.asarray(blocks).shape[0], 8), dtype=np.uint32)
        return zw if hz is None else np.concatenate([zw, hz])

    def fake_round_kernel(zw, r, s, v, claimed, table, live, plo, phi,
                         p_lo, p_hi, s_lo, s_hi):
        mask = mask_fn(np.asarray(live))
        b = mask.shape[0] // 2
        # quorum: count of valid lanes per half vs the lo threshold
        return mask, mask[:b].sum() >= int(p_lo), mask[b:].sum() >= int(s_lo)

    monkeypatch.setattr(batch_mod, "_digest_kernel", fake_digest)
    monkeypatch.setattr(batch_mod, "_round_kernel", fake_round_kernel)


def test_output_index_mapping_with_malformed_lanes(monkeypatch):
    dev, msgs, phash, seals = _fixture()
    # malform: msg[1] wrong-length signature, seal[2] wrong-length signer —
    # these never reach the kernel and stay False in the scattered output.
    msgs[1].signature = b"\x01" * 10
    seals[2] = CommittedSeal(signer=b"short", signature=seals[2].signature)

    _stub_kernels(monkeypatch, lambda live: live.copy())  # all live lanes ok
    sm, p_ok, cm, c_ok = dev.certify_round(msgs, phash, seals, height=3)
    assert list(sm) == [True, False, True, True]
    assert list(cm) == [True, True, False, True]
    assert p_ok and c_ok  # 3 >= quorum 3


def test_kernel_mask_scatters_to_original_positions(monkeypatch):
    dev, msgs, phash, seals = _fixture()

    def half_bad(live):
        mask = live.copy()
        lanes = mask.shape[0] // 2
        mask[0] = False  # first prepare lane
        mask[lanes + 1] = False  # second seal lane
        return mask

    _stub_kernels(monkeypatch, half_bad)
    sm, _, cm, _ = dev.certify_round(msgs, phash, seals, height=3)
    assert list(sm) == [False, True, True, True]
    assert list(cm) == [True, False, True, True]


def test_degenerate_no_seals_falls_back(monkeypatch):
    dev, msgs, phash, seals = _fixture()
    calls = []

    def fake_certify_senders(m, height, threshold=None):
        calls.append(("senders", len(m), threshold))
        return np.ones(len(m), dtype=bool), True

    monkeypatch.setattr(dev, "certify_senders", fake_certify_senders)
    sm, p_ok, cm, c_ok = dev.certify_round(msgs, phash, [], height=3)
    assert calls == [("senders", 4, 2)] or calls == [("senders", 4, None)]
    assert p_ok and list(sm) == [True] * 4
    assert cm.size == 0 and c_ok is False  # quorum 3 > 0 unreachable with no seals


def test_degenerate_no_messages_falls_back(monkeypatch):
    dev, msgs, phash, seals = _fixture()

    def fake_certify_seals(ph, s, height, threshold=None):
        return np.ones(len(s), dtype=bool), True

    monkeypatch.setattr(dev, "certify_seals", fake_certify_seals)
    sm, p_ok, cm, c_ok = dev.certify_round([], phash, seals, height=3)
    assert sm.size == 0 and p_ok is False
    assert list(cm) == [True] * 4 and c_ok
