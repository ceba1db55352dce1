"""Byte-identity of the vectorized packers vs the reference loop packers.

The vectorized data plane (verify/batch.py, ops/keccak.py::pack_messages)
must produce BIT-IDENTICAL arrays to the kept per-message loop packers
(``_pack_*_reference``) across batch buckets, oversize payloads, corrupt
lanes, and padding edges — same contract as the host/device mask parity:
the packing rewrite must be invisible to the kernels.  Plus the empty-
input guards (n=0 used to raise through ``max()``) and the round-scoped
:class:`~go_ibft_tpu.verify.pipeline.PackCache` semantics.
"""

import gc
import random

import numpy as np
import pytest

from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
from go_ibft_tpu.messages.helpers import CommittedSeal, extract_committed_seal
from go_ibft_tpu.messages.wire import Proposal, View
from go_ibft_tpu.ops.keccak import (
    _pack_messages_reference,
    addresses_to_words,
    pack_messages,
)
from go_ibft_tpu.verify.batch import (
    SIG_BYTES,
    _pack_seal_batch_reference,
    _pack_sender_batch_reference,
    pack_seal_batch,
    pack_sender_batch,
    split_signature,
)
from go_ibft_tpu.verify.pipeline import PackCache, SenderPack


def _signed(n, height=1, seed=0):
    keys = [PrivateKey.from_seed(b"pv-%d-%d" % (seed, i)) for i in range(n)]
    powers = {k.address: 1 for k in keys}
    src = ECDSABackend.static_validators(powers)
    backends = [ECDSABackend(k, src) for k in keys]
    view = View(height=height, round=0)
    phash = proposal_hash_of(Proposal(raw_proposal=b"pv block", round=0))
    prepares = [b.build_prepare_message(phash, view) for b in backends]
    seals = [
        extract_committed_seal(b.build_commit_message(phash, view))
        for b in backends
    ]
    return prepares, seals, phash


def _assert_tuples_identical(a, b):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, f"part {i}: {x.dtype} != {y.dtype}"
        assert x.shape == y.shape, f"part {i}: {x.shape} != {y.shape}"
        assert np.array_equal(x, y), f"part {i} differs"


# -- sender/seal batch parity ------------------------------------------------


@pytest.mark.parametrize("n", [1, 3, 7, 8, 9, 32, 33])
def test_sender_batch_parity_across_buckets(n):
    prepares, _, _ = _signed(n)
    if n >= 3:  # corrupt-signature lane: parity must hold bit-for-bit
        sig = bytearray(prepares[2].signature)
        sig[5] ^= 0xFF
        prepares[2].signature = bytes(sig)
    _assert_tuples_identical(
        pack_sender_batch(prepares), _pack_sender_batch_reference(prepares)
    )


@pytest.mark.parametrize("n", [1, 4, 8, 9])
def test_seal_batch_parity_across_buckets(n):
    _, seals, phash = _signed(n)
    if n >= 2:  # garbage signature of the right length
        seals[1] = CommittedSeal(signer=seals[1].signer, signature=b"\x01" * 65)
    _assert_tuples_identical(
        pack_seal_batch(phash, seals), _pack_seal_batch_reference(phash, seals)
    )


def test_sender_batch_parity_with_pad_lanes_and_payload_override():
    prepares, _, _ = _signed(3)
    payloads = [m.encode(include_signature=False) for m in prepares]
    payloads[1] = b""  # the oversize path substitutes empty payloads
    _assert_tuples_identical(
        pack_sender_batch(prepares, pad_lanes=32, payloads=payloads),
        _pack_sender_batch_reference(prepares, pad_lanes=32, payloads=payloads),
    )


def test_sender_batch_parity_oversize_payload_rides_next_bucket():
    """A multi-block payload (well under the bucket max) packs identically."""
    prepares, _, _ = _signed(2)
    payloads = [m.encode(include_signature=False) for m in prepares]
    payloads[0] = bytes(range(256)) * 4  # 1024B -> 8 rate blocks
    _assert_tuples_identical(
        pack_sender_batch(prepares, payloads=payloads),
        _pack_sender_batch_reference(prepares, payloads=payloads),
    )


def test_sender_batch_too_big_payload_raises_like_reference():
    prepares, _, _ = _signed(1)
    payloads = [bytes(10_000)]  # > largest block bucket
    with pytest.raises(ValueError):
        pack_sender_batch(prepares, payloads=payloads)
    with pytest.raises(ValueError):
        _pack_sender_batch_reference(prepares, payloads=payloads)


# -- empty-input guards ------------------------------------------------------


def test_empty_sender_batch_is_fully_dead():
    blocks, counts, r, s, v, senders, live = pack_sender_batch([])
    assert blocks.shape == (8, 2, 17, 2) and not blocks.any()
    assert counts.shape == (8,) and (counts == 1).all()
    assert not live.any()
    assert not r.any() and not s.any() and not v.any() and not senders.any()


def test_empty_sender_batch_respects_pad_lanes():
    out = pack_sender_batch([], pad_lanes=32)
    assert out[0].shape[0] == 32 and not out[6].any()


def test_empty_seal_batch_is_fully_dead():
    phash = b"\x07" * 32
    hz, r, s, v, signers, live = pack_seal_batch(phash, [])
    assert hz.shape == (8, 8)
    # the hash still broadcasts (same layout as the reference's n>0 path)
    expect = np.frombuffer(phash, ">u4")[::-1].astype(np.uint32)
    assert (hz == expect).all()
    assert not live.any() and not signers.any()


def test_bucket_boundary_counts():
    """n exactly at / one past a lane bucket pads to the right shapes."""
    for n, want in ((8, 8), (9, 32)):
        prepares, seals, phash = _signed(n)
        assert pack_sender_batch(prepares)[0].shape[0] == want
        assert pack_seal_batch(phash, seals)[0].shape[0] == want


# -- block packing parity ----------------------------------------------------


def test_pack_messages_parity_edge_lengths():
    rng = random.Random(7)
    cases = [
        [b""],
        [b"x"],
        [bytes(135)],
        [bytes(136)],
        [bytes(137)],
        [bytes([rng.randrange(256) for _ in range(rng.randrange(0, 300))]) for _ in range(17)],
        [b"y" * 64] * 9,  # uniform-length fast path
    ]
    for payloads in cases:
        for max_blocks in (2, 8):
            a = pack_messages(payloads, max_blocks)
            b = _pack_messages_reference(payloads, max_blocks)
            assert np.array_equal(a[0], b[0])
            assert np.array_equal(a[1], b[1]) and a[1].dtype == b[1].dtype


def test_pack_messages_oversize_raises_both():
    for fn in (pack_messages, _pack_messages_reference):
        with pytest.raises(ValueError):
            fn([bytes(300)], 2)


def test_addresses_to_words_matches_scalar_and_validates():
    from go_ibft_tpu.ops.keccak import address_to_words

    addrs = [bytes([i]) * 20 for i in range(5)]
    bulk = addresses_to_words(addrs)
    for i, a in enumerate(addrs):
        assert (bulk[i] == address_to_words(a)).all()
    with pytest.raises(ValueError):
        addresses_to_words([b"\x01" * 19])
    assert addresses_to_words([]).shape == (0, 5)


# -- split_signature round trip ---------------------------------------------


def _rt_case(r, s, v):
    sig = r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])
    assert split_signature(sig) == (r, s, v)
    # and the vectorized splitter agrees limb-for-limb with the loop path
    from go_ibft_tpu.ops import secp256k1 as sec
    from go_ibft_tpu.ops.fields import to_limbs
    from go_ibft_tpu.verify.batch import _split_signatures, _words_to_limbs

    rw, sw, vv = _split_signatures([sig])
    nl = sec.FIELD.nlimbs
    assert np.array_equal(_words_to_limbs(rw, nl), to_limbs([r], nl))
    assert np.array_equal(_words_to_limbs(sw, nl), to_limbs([s], nl))
    assert int(vv[0]) == v


try:
    from hypothesis import given
    from hypothesis import strategies as st

    @given(
        st.integers(min_value=0, max_value=(1 << 256) - 1),
        st.integers(min_value=0, max_value=(1 << 256) - 1),
        st.integers(min_value=0, max_value=255),
    )
    def test_split_signature_round_trip(r, s, v):
        _rt_case(r, s, v)

except ImportError:  # hypothesis absent: seeded-random fallback, same property

    def test_split_signature_round_trip():
        rng = random.Random(1234)
        edge = [0, 1, (1 << 256) - 1, (1 << 255), (1 << 13) - 1, 1 << 13]
        values = edge + [rng.getrandbits(256) for _ in range(64)]
        for r in values[:8]:
            for s in values[:8]:
                _rt_case(r, s, rng.randrange(256))
        for _ in range(64):
            _rt_case(rng.getrandbits(256), rng.getrandbits(256), rng.randrange(256))


def test_split_signature_rejects_wrong_length():
    with pytest.raises(ValueError):
        split_signature(b"\x00" * 64)
    from go_ibft_tpu.verify.batch import _split_signatures

    with pytest.raises(ValueError):
        _split_signatures([b"\x00" * SIG_BYTES, b"\x00" * 64])


# -- pack cache --------------------------------------------------------------


def test_pack_cache_hit_skips_reencode_and_stays_identical():
    prepares, _, _ = _signed(4)
    cache = PackCache()
    cold = pack_sender_batch(prepares, cache=cache)
    assert len(cache) == 4

    encodes = []
    orig = type(prepares[0]).encode

    def counting_encode(self, **kw):
        encodes.append(1)
        return orig(self, **kw)

    type(prepares[0]).encode = counting_encode
    try:
        warm = pack_sender_batch(prepares, cache=cache)
    finally:
        type(prepares[0]).encode = orig
    assert encodes == []  # no message re-encoded on a warm cache
    _assert_tuples_identical(warm, cold)
    _assert_tuples_identical(warm, _pack_sender_batch_reference(prepares))


def test_pack_cache_signature_mutation_is_a_miss():
    prepares, _, _ = _signed(2)
    cache = PackCache()
    pack_sender_batch(prepares, cache=cache)
    sig = bytearray(prepares[0].signature)
    sig[5] ^= 0xFF
    prepares[0].signature = bytes(sig)
    assert cache.lookup(prepares[0]) is None  # token mismatch
    # re-pack picks up the new signature and matches the reference exactly
    _assert_tuples_identical(
        pack_sender_batch(prepares, cache=cache),
        _pack_sender_batch_reference(prepares),
    )


def test_pack_cache_round_scoped_eviction_oldest_first():
    cache = PackCache(cap=4)

    class _Msg:
        def __init__(self, tag):
            self.sender = b"\x01" * 20
            self.signature = bytes([tag]) * 65

    def lane(payload):
        z = np.zeros(20, np.int32)
        return SenderPack(payload, z, z, 0, np.zeros(5, np.uint32))

    keep = []
    for round_, tags in ((0, (1, 2)), (1, (3, 4))):
        cache.note_round(round_)
        for t in tags:
            m = _Msg(t)
            keep.append(m)
            cache.store(m, lane(b"p%d" % t))
    assert len(cache) == 4
    cache.note_round(2)
    extra = _Msg(9)
    keep.append(extra)
    cache.store(extra, lane(b"p9"))
    # cap 4: round-0 entries (the oldest round) evicted wholesale first
    assert cache.lookup(keep[0]) is None and cache.lookup(keep[1]) is None
    assert cache.lookup(keep[2]) is not None
    assert cache.lookup(extra) is not None


def test_pack_cache_dead_object_entry_is_dropped():
    cache = PackCache()
    prepares, _, _ = _signed(1)
    pack_sender_batch(prepares, cache=cache)
    assert len(cache) == 1
    del prepares
    gc.collect()
    assert len(cache) == 0  # weakref death callback pruned the entry


def test_pack_cache_clear_and_note_round():
    prepares, _, _ = _signed(2)
    cache = PackCache()
    cache.note_round(3)
    pack_sender_batch(prepares, cache=cache)
    assert len(cache) == 2
    cache.clear()
    assert len(cache) == 0
    assert cache.lookup(prepares[0]) is None


# -- malformed-lane validation (ISSUE 3 satellite) ---------------------------
# The vectorized packers must never die in an opaque numpy frombuffer /
# reshape error: wrong-length signatures and addresses are validated up
# front and raise MalformedLaneError NAMING the lane, at exactly the inputs
# where the reference loop packers also raise (parity pinned both ways).


def test_pack_sender_batch_malformed_signature_names_lane():
    from go_ibft_tpu.verify.batch import MalformedLaneError

    prepares, _, _ = _signed(4)
    prepares[2].signature = prepares[2].signature[:40]  # truncated
    with pytest.raises(MalformedLaneError) as err:
        pack_sender_batch(prepares)
    assert err.value.lane == 2
    assert err.value.field == "signature"
    # the reference loop packer raises on the same batch (parity: the
    # vectorized path rejects exactly what the oracle rejects)
    with pytest.raises(ValueError):
        _pack_sender_batch_reference(prepares)
    # MalformedLaneError IS a ValueError: pre-existing callers still catch
    assert isinstance(err.value, ValueError)


def test_pack_sender_batch_malformed_sender_names_lane():
    from go_ibft_tpu.verify.batch import MalformedLaneError

    prepares, _, _ = _signed(3)
    prepares[1].sender = b"short"
    with pytest.raises(MalformedLaneError) as err:
        pack_sender_batch(prepares)
    assert (err.value.lane, err.value.field) == (1, "sender")
    with pytest.raises(ValueError):
        _pack_sender_batch_reference(prepares)


def test_pack_seal_batch_malformed_lanes_and_hash():
    from go_ibft_tpu.verify.batch import MalformedLaneError

    _, seals, phash = _signed(3)
    bad = list(seals)
    bad[1] = CommittedSeal(signer=bad[1].signer, signature=b"\x01" * 30)
    with pytest.raises(MalformedLaneError) as err:
        pack_seal_batch(phash, bad)
    assert (err.value.lane, err.value.field) == (1, "signature")
    with pytest.raises(ValueError):
        _pack_seal_batch_reference(phash, bad)

    bad_signer = list(seals)
    bad_signer[2] = CommittedSeal(signer=b"x" * 7, signature=seals[2].signature)
    with pytest.raises(MalformedLaneError) as err:
        pack_seal_batch(phash, bad_signer)
    assert (err.value.lane, err.value.field) == (2, "signer")

    # a wrong-length proposal hash is batch-wide, not a lane: typed
    # ValueError instead of the old frombuffer crash
    with pytest.raises(ValueError, match="proposal hash"):
        pack_seal_batch(b"\x11" * 31, seals)


def test_split_signatures_is_malformed_lane_error():
    from go_ibft_tpu.verify.batch import MalformedLaneError, _split_signatures

    with pytest.raises(MalformedLaneError) as err:
        _split_signatures([b"\x00" * SIG_BYTES, b"\x00" * 64])
    assert err.value.lane == 1


def test_valid_batches_still_bit_identical_after_validation():
    """The added validation must not change a single bit of valid packs."""
    prepares, seals, phash = _signed(5)
    _assert_tuples_identical(
        pack_sender_batch(prepares), _pack_sender_batch_reference(prepares)
    )
    _assert_tuples_identical(
        pack_seal_batch(phash, seals), _pack_seal_batch_reference(phash, seals)
    )


def test_pack_cache_evict_on_quarantine():
    """A quarantined lane's cached pack must be evicted so a corrected
    re-send is never served the condemned lane (ISSUE 3 satellite)."""
    prepares, _, _ = _signed(3)
    cache = PackCache()
    pack_sender_batch(prepares, cache=cache)
    assert len(cache) == 3
    cache.evict(prepares[1])
    assert len(cache) == 2
    assert cache.lookup(prepares[1]) is None
    assert cache.lookup(prepares[0]) is not None
    # evicting an uncached message is a no-op, not an error
    cache.evict(prepares[1])
    assert len(cache) == 2


# -- rows built in bulk (ISSUE 36) --------------------------------------------
# The joint layouts of ``DeviceBatchVerifier.verify_senders`` are held, bit
# for bit, to the reference loop packers: what a dispatch receives is the
# envelope rows of ``_pack_sender_batch_reference`` and the seal rows of
# ``_pack_seal_batch_reference``.  No signature here is valid, and none has
# to be: a packer moves bytes.


def _flood(n, kind, seed=5):
    """``n`` COMMITs (or PREPAREs) of one height over one hash, random bytes
    in every signed field; returns ``(msgs, phash)``."""
    from go_ibft_tpu.messages.wire import (
        CommitMessage,
        IbftMessage,
        MessageType,
        PrepareMessage,
    )

    rng = random.Random(seed * 1000 + n)
    phash = rng.randbytes(32)
    msgs = []
    for _ in range(n):
        msg = IbftMessage(
            view=View(height=3, round=0),
            sender=rng.randbytes(20),
            signature=rng.randbytes(SIG_BYTES),
        )
        if kind == "commit":
            msg.type = MessageType.COMMIT
            msg.commit_data = CommitMessage(
                proposal_hash=phash, committed_seal=rng.randbytes(SIG_BYTES)
            )
        else:
            msg.type = MessageType.PREPARE
            msg.prepare_data = PrepareMessage(proposal_hash=phash)
        msgs.append(msg)
    return msgs, phash


@pytest.fixture
def captured(monkeypatch):
    """A device verifier whose programs are stand-ins that keep what they
    were handed: ``digests`` (blocks, counts) and ``dispatches`` (the six
    arrays of a recover dispatch), in order.  The digest rows are the
    blocks' first eight words, so that a row of ``zw`` names its message."""
    import jax.numpy as jnp

    from go_ibft_tpu.verify import DeviceBatchVerifier, batch

    got = {"digests": [], "joined": [], "dispatches": []}

    def digest(blocks, counts, hz=None):
        got["digests"].append((blocks, counts))
        got["joined"].append(hz)
        zw = np.asarray(blocks).reshape(len(counts), -1)[:, :8]
        return jnp.asarray(zw if hz is None else np.concatenate([zw, hz]))

    def recover(zw, r, s, v, claimed, table, live):
        got["dispatches"].append(
            tuple(np.asarray(a) for a in (zw, r, s, v, claimed, live))
        )
        return np.asarray(live, dtype=bool)

    monkeypatch.setattr(batch, "_digest_kernel", digest)
    monkeypatch.setattr(batch, "_recover_kernel", recover)
    powers = {bytes([i]) * 20: 1 for i in range(1, 5)}
    got["verifier"] = DeviceBatchVerifier(lambda height: powers)
    return got


def _seals_of(commits):
    return [
        CommittedSeal(signer=m.sender, signature=m.commit_data.committed_seal)
        for m in commits
    ]


@pytest.mark.parametrize("kind", ["commit", "prepare"])
def test_joint_layout_of_a_100_validator_flood_is_the_reference_packers(
    kind, captured
):
    """99 envelopes and (COMMITs) their 99 seals in ONE 256-lane dispatch:
    lanes [0, 128) the reference sender pack, lanes [128, 256) the
    reference seal pack (a PREPARE flood leaves that half dead)."""
    msgs, phash = _flood(99, kind)
    dev = captured["verifier"]
    assert dev.verify_senders(msgs).all()
    ((blocks, counts),) = captured["digests"]
    (got,) = captured["dispatches"]
    want = _pack_sender_batch_reference(msgs, pad_lanes=128)
    _assert_tuples_identical((blocks, counts), want[:2])
    seals = _seals_of(msgs) if kind == "commit" else []
    hz, r2, s2, v2, signers, live2 = _pack_seal_batch_reference(
        phash, seals, pad_lanes=128
    )
    hz[len(seals) :] = 0  # a dead lane carries no hash (pack_seal_lanes)
    zw = np.concatenate([blocks.reshape(128, -1)[:, :8], hz])
    joint = (zw,) + tuple(
        np.concatenate([a, b]) for a, b in zip(want[2:], (r2, s2, v2, signers, live2))
    )
    assert joint[0].shape == (256, 8) and int(joint[5].sum()) == 99 + len(seals)
    _assert_tuples_identical(got, joint)


def test_joint_layout_of_a_300_validator_flood_is_the_reference_packers(captured):
    """299 COMMITs fold already (512 lanes): their 299 seals are the drain's
    next chunk, at 512 lanes too."""
    msgs, phash = _flood(299, "commit")
    dev = captured["verifier"]
    assert dev.verify_senders(msgs).all()
    ((blocks, counts),) = captured["digests"]
    envelopes, seals = captured["dispatches"]
    want = _pack_sender_batch_reference(msgs)
    assert want[0].shape[0] == 512
    _assert_tuples_identical((blocks, counts), want[:2])
    _assert_tuples_identical(
        envelopes, (blocks.reshape(512, -1)[:, :8],) + want[2:]
    )
    ref = list(_pack_seal_batch_reference(phash, _seals_of(msgs), pad_lanes=512))
    ref[0][299:] = 0
    _assert_tuples_identical(seals, tuple(ref))


def test_joint_layout_with_cache_hits_riders_apart_and_odd_messages(captured):
    """The same layout where some lanes are cache hits, only some COMMITs
    ride, and a message of another shape goes through the generic encoder."""
    from go_ibft_tpu.messages.wire import Proposal, PrePrepareMessage, MessageType
    from go_ibft_tpu.utils import metrics
    from go_ibft_tpu.verify import batch

    commits, phash = _flood(20, "commit")
    prepares, _ = _flood(5, "prepare", seed=6)
    dev = captured["verifier"]
    dev.verify_senders(commits[:7])  # lanes that will hit; their seals are judged
    odd = commits[9]
    odd.type = MessageType.PREPREPARE
    odd.commit_data = None
    odd.preprepare_data = PrePrepareMessage(
        proposal=Proposal(raw_proposal=b"a block", round=0), proposal_hash=phash
    )
    commits[12].commit_data.committed_seal = b"\x01" * 64  # does not ride
    msgs = prepares[:2] + commits + prepares[2:]
    generic = metrics.get_counter(batch.PACK_GENERIC_KEY)
    del captured["digests"][:], captured["dispatches"][:]
    assert dev.verify_senders(msgs).all()
    # The PREPREPARE and the COMMIT with a 64-byte seal: no plain shape.
    assert metrics.get_counter(batch.PACK_GENERIC_KEY) == generic + 2
    ((blocks, counts),) = captured["digests"]
    (got,) = captured["dispatches"]
    want = _pack_sender_batch_reference(msgs, pad_lanes=32)
    _assert_tuples_identical((blocks, counts), want[:2])
    riders = [m for m in commits[7:] if m is not odd and m is not commits[12]]
    ref = list(_pack_seal_batch_reference(phash, _seals_of(riders), pad_lanes=32))
    ref[0][len(riders) :] = 0
    joint = (np.concatenate([blocks.reshape(32, -1)[:, :8], ref[0]]),) + tuple(
        np.concatenate([a, b]) for a, b in zip(want[2:], ref[1:])
    )
    _assert_tuples_identical(got, joint)


# -- the pack cache takes a drain's rows by reference -------------------------


def _lane_of(pack):
    return (
        pack.payload,
        pack.r_limbs.tolist(),
        pack.s_limbs.tolist(),
        pack.v,
        pack.sender_words.tolist(),
    )


def _fresh_lanes(msgs):
    _, _, r, s, v, senders, _ = _pack_sender_batch_reference(msgs)
    payloads = [m.encode(include_signature=False) for m in msgs]
    return [
        (payloads[i], r[i].tolist(), s[i].tolist(), int(v[i]), senders[i].tolist())
        for i in range(len(msgs))
    ]


def test_a_hit_after_a_by_reference_store_is_a_fresh_pack():
    msgs, _ = _flood(9, "commit")
    cache = PackCache()
    calls = []
    for name in ("store_rows", "lookup_many"):
        orig = getattr(cache, name)
        setattr(cache, name, lambda *a, _o=orig, _n=name: (calls.append(_n), _o(*a))[1])
    pack_sender_batch(msgs, cache=cache)
    # One lookup and one store for the drain, each under one lock; no lane
    # is copied at store: an entry is the drain's arrays and a row index.
    assert calls == ["lookup_many", "store_rows"] and len(cache) == 9
    hits = cache.lookup_many(msgs)
    assert [_lane_of(h) for h in hits] == _fresh_lanes(msgs)
    assert all(h.r_limbs.base is not None for h in hits)  # views, not copies
    assert (cache.hits, cache.misses) == (9, 9)
    assert _lane_of(cache.lookup(msgs[4])) == _fresh_lanes(msgs)[4]


def test_a_later_drain_leaves_an_earlier_drains_cached_rows_as_they_were(captured):
    """The drain's arrays are never written after they are packed: here
    they are made read-only the moment the dispatch has them, and every
    later pack (hits among fresh lanes, another drain, the same lanes in
    another order) still runs and still reads the first drain's lanes."""
    dev = captured["verifier"]
    cache = dev._pack_cache
    first, _ = _flood(11, "commit")
    second, _ = _flood(11, "prepare", seed=8)
    stored, frozen = [], []
    store_rows = cache.store_rows

    def noting_store(msgs, rows, at):
        for a in (rows.r_limbs, rows.s_limbs, rows.v, rows.sender_words):
            stored.append(a if a.base is None else a.base)
        store_rows(msgs, rows, at)

    def freeze():  # every array a finished drain left in the cache
        while stored:
            base = stored.pop()
            base.setflags(write=False)
            frozen.append((base, base.copy()))

    cache.store_rows = noting_store
    dev.verify_senders(first)
    freeze()
    assert len(frozen) == 4 and all(base.shape[0] == 64 for base, _ in frozen)
    want = _fresh_lanes(first)
    assert [_lane_of(h) for h in cache.lookup_many(first)] == want
    dev.verify_senders(second)
    freeze()
    dev.verify_senders(first[5:] + second[:3] + _flood(4, "commit", seed=9)[0])
    freeze()
    dev.verify_senders(list(reversed(first)))
    pack_sender_batch(first[2:6], cache=cache, pad_lanes=64)
    freeze()
    assert len(frozen) == 12  # the two all-hit packs stored nothing
    for base, was in frozen:
        assert np.array_equal(base, was)
    assert [_lane_of(h) for h in cache.lookup_many(first)] == want
    # And what a dispatch received for the reversed drain is a fresh pack's.
    got = captured["dispatches"][-1]
    ref = _pack_sender_batch_reference(list(reversed(first)), pad_lanes=32)
    _assert_tuples_identical(tuple(a[:32] for a in got[1:]), ref[2:])


def test_what_a_launch_was_handed_is_as_it_was_after_the_next_drains_packed(captured):
    """The rows go to the compiled calls as the numpy arrays the packers
    wrote, and the runtime may read one after the call returned (ISSUE 38):
    every array a launch was handed (the digest's blocks, counts and hash
    rows, a recover's five rows and its host ``zw``) is made read-only the
    moment the stand-in has it, and the drains that follow (warm cache hits
    among fresh lanes, a seal drain, the first drain again) pack without
    writing to one."""
    dev = captured["verifier"]
    first, phash = _flood(11, "commit")
    second, _ = _flood(6, "prepare", seed=8)
    frozen = []

    def freeze():
        handed = [a for pair in captured["digests"] for a in pair]
        handed += [a for a in captured["joined"] if a is not None]
        handed += [a for rows in captured["dispatches"] for a in rows[1:]]
        handed += [
            rows[0] for rows in captured["dispatches"] if rows[0].flags.writeable
        ]  # a host ``zw`` (the stand-in's copy of a device one is not)
        for a in handed:
            assert type(a) is np.ndarray and a.base is None  # whole, not a view
            a.setflags(write=False)
            frozen.append((a, a.copy()))
        for got in ("digests", "joined", "dispatches"):
            del captured[got][:]

    dev.verify_senders(first)
    freeze()
    assert len(frozen) == 2 + 1 + 5  # blocks, counts; hz; r, s, v, claimed, live
    hits = dev._pack_cache.hits
    dev.verify_senders(first[3:8] + second)  # warm hits among fresh lanes
    assert dev._pack_cache.hits == hits + 5
    freeze()
    others = _seals_of(_flood(9, "commit", seed=13)[0])  # no verdict: dispatched
    dev.verify_committed_seals(phash, others, 3)
    freeze()
    assert len(frozen) == 2 * 8 + 6  # the seal drain: hash rows and five rows
    dev.verify_senders(list(reversed(first)))
    freeze()
    for a, was in frozen:
        assert np.array_equal(a, was)


def test_a_drain_after_warm_up_meets_no_call_form_the_warm_up_did_not_load(
    monkeypatch,
):
    """``warmup`` calls each program in the forms the drains use (host rows
    as numpy, ``zw`` a host array or the digest program's output, the table
    device-resident): at 128 + 128 lanes (a 100-validator flood), 512 (a
    300-validator one, its seals the next chunk) and 2,048 (a sync chunk) no
    drain grows a program's call cache, which is what the cost ledger
    counts as a compile.  The recover program is a cheap jit of the same
    signature here (no ladder compiles); the digest is the real function."""
    import jax

    from go_ibft_tpu.verify import DeviceBatchVerifier, batch

    def recover(zw, r, s, v, claimed, table, live):
        return live & (zw[:, 0] == r[:, 0].astype(np.uint32)) & (table[0, 0] == 0)

    monkeypatch.setattr(batch, "_recover_kernel", jax.jit(recover))
    # A function of its own, so that only this test's calls are in its cache
    # (jits of one function share theirs).
    monkeypatch.setattr(
        batch, "_digest_kernel", jax.jit(lambda *a: batch.digest_words(*a))
    )
    powers = {bytes([i // 256, i % 256]) * 10: 1 for i in range(300)}
    dev = DeviceBatchVerifier(lambda height: powers)
    dev.warmup(lanes=(128, 256, 512, 2048), blocks=(2,), table_rows=512)
    programs = (batch._recover_kernel, batch._digest_kernel)
    loaded = [k._cache_size() for k in programs]
    assert loaded == [2 * 4, 4 + 1]  # two forms a width; one joined digest

    commits, phash = _flood(99, "commit")
    dev.verify_senders(commits)  # 128 + 128: the digest joins the halves
    dev.verify_committed_seals(phash, _seals_of(_flood(99, "commit", seed=6)[0]), 3)
    flood, _ = _flood(299, "commit", seed=7)
    dev.verify_senders(flood)  # 512, and the seals' 512 behind it
    lanes = [(phash, seal) for seal in _seals_of(flood)] * 7
    assert len(lanes[:2048]) == 2048
    dev.verify_seal_lanes(lanes[:2048], 3)
    assert [k._cache_size() for k in programs] == loaded


def test_by_reference_entries_guard_a_recycled_id():
    cache = PackCache()
    (victim, other), _ = _flood(2, "prepare", seed=11)
    pack_sender_batch([victim, other], cache=cache)
    mid = id(victim)
    del victim
    gc.collect()
    # No death callback ran: the entry is there until the cache is counted.
    assert mid in cache._index
    # A new message at the dead one's address (here: the dead entry re-keyed
    # under a live message's id, which is what a recycled id looks like) ...
    (fresh,), _ = _flood(1, "prepare", seed=12)
    tag = cache._index.pop(mid)
    cache._index[id(fresh)] = tag
    cache._by_round[tag][id(fresh)] = cache._by_round[tag].pop(mid)
    # ... never reads the dead one's rows, and takes the entry over.
    assert cache.lookup(fresh) is None
    pack_sender_batch([fresh], cache=cache)
    assert _lane_of(cache.lookup(fresh)) == _fresh_lanes([fresh])[0]
    assert _lane_of(cache.lookup(other)) == _fresh_lanes([other])[0]
    assert len(cache) == 2
    # Counting drops what died.
    del fresh
    gc.collect()
    assert len(cache._index) == 2 and len(cache) == 1 == len(cache._index)
    assert cache.lookup(other) is not None


def test_by_reference_stores_keep_evict_cap_and_owner_order():
    """``evict``, the cap and the per-owner eviction order, with entries
    stored a drain at a time (the single-lane cases above and in
    ``tests/test_sched.py`` stay as they are)."""
    cache = PackCache(cap=6)
    drains = {}

    def drain(name, n, owner, seed):
        msgs, _ = _flood(n, "prepare", seed=seed)
        drains[name] = msgs
        with cache.owned(owner):
            pack_sender_batch(msgs, cache=cache)

    cache.note_round(3, owner="a")
    cache.note_round(8, owner="b")
    drain("a3", 3, "a", 21)
    drain("b8", 3, "b", 22)
    assert len(cache) == 6
    cache.evict(drains["a3"][1])  # quarantine: one lane of a drain goes
    assert len(cache) == 5 and cache.lookup(drains["a3"][1]) is None
    assert cache.lookup(drains["a3"][0]) is not None
    cache.evict(drains["a3"][1])  # twice is a no-op
    assert len(cache) == 5
    # A's rotation kills its round-3 entries; cap pressure takes them whole,
    # before either owner's live round gives anything up.
    cache.note_round(4, owner="a")
    drain("b8+", 2, "b", 23)
    assert all(cache.lookup(m) is None for m in drains["a3"])
    assert all(cache.lookup(m) is not None for m in drains["b8"] + drains["b8+"])
    assert len(cache) == 5
    # Only live rounds left: the oldest live round sheds, first in first out.
    drain("a4", 3, "a", 24)
    assert len(cache) == 6
    assert [cache.lookup(m) is None for m in drains["a4"]] == [True, True, False]
    assert all(cache.lookup(m) is not None for m in drains["b8"] + drains["b8+"])
    # One owner's reset drops its entries only.
    cache.clear(owner="b")
    assert len(cache) == 1 and cache.lookup(drains["a4"][2]) is not None
    # A message stored again moves to its new drain's rows.
    again = drains["a4"][2]
    again.signature = bytes(SIG_BYTES)
    assert cache.lookup(again) is None  # the token guards the old rows
    with cache.owned("a"):
        pack_sender_batch([again], cache=cache)
    assert len(cache) == 1
    assert _lane_of(cache.lookup(again)) == _fresh_lanes([again])[0]
