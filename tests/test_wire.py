"""Wire-codec tests: byte-level vectors + round-trips.

The byte-level vectors are hand-derived from the protobuf wire format so that
``payload_no_sig`` stays byte-compatible with the Go reference's
``proto.Marshal`` output (reference messages/proto/helper.go:13-27).
"""

import pytest

from go_ibft_tpu.messages import (
    CommitMessage,
    IbftMessage,
    MessageType,
    PreparedCertificate,
    PrepareMessage,
    PrePrepareMessage,
    Proposal,
    RoundChangeCertificate,
    RoundChangeMessage,
    View,
)


def test_view_encoding_bytes():
    assert View(height=1, round=2).encode() == b"\x08\x01\x10\x02"
    # proto3 zero values are omitted entirely
    assert View(height=0, round=0).encode() == b""
    # multi-byte varint: 300 = 0xAC 0x02
    assert View(height=300, round=0).encode() == b"\x08\xac\x02"


def test_proposal_encoding_bytes():
    assert Proposal(raw_proposal=b"ab", round=3).encode() == b"\x0a\x02ab\x10\x03"
    assert Proposal().encode() == b""


def test_ibft_message_encoding_bytes():
    msg = IbftMessage(
        view=View(height=1, round=2),
        sender=b"\x01",
        signature=b"\xff",
        type=MessageType.COMMIT,
        commit_data=CommitMessage(proposal_hash=b"h", committed_seal=b"s"),
    )
    expected = (
        b"\x0a\x04\x08\x01\x10\x02"  # view
        b"\x12\x01\x01"  # from
        b"\x1a\x01\xff"  # signature
        b"\x20\x02"  # type = COMMIT
        b"\x3a\x06\x0a\x01h\x12\x01s"  # commit payload
    )
    assert msg.encode() == expected
    # payload_no_sig drops exactly the signature field
    assert msg.payload_no_sig() == (
        b"\x0a\x04\x08\x01\x10\x02" b"\x12\x01\x01" b"\x20\x02" b"\x3a\x06\x0a\x01h\x12\x01s"
    )
    # and does not mutate the message
    assert msg.signature == b"\xff"


def test_preprepare_type_zero_omitted():
    # type = PREPREPARE = 0 is a proto3 default: omitted on the wire
    msg = IbftMessage(
        view=View(height=5, round=0),
        sender=b"A",
        type=MessageType.PREPREPARE,
        preprepare_data=PrePrepareMessage(
            proposal=Proposal(raw_proposal=b"block", round=0),
            proposal_hash=b"H",
        ),
    )
    raw = msg.encode()
    assert b"\x20" not in raw[:8]  # no type tag
    decoded = IbftMessage.decode(raw)
    assert decoded.type == MessageType.PREPREPARE
    assert decoded.preprepare_data.proposal.raw_proposal == b"block"


def test_set_but_empty_nested_message_is_encoded():
    # Go pointer semantics: a set-but-empty message must be distinguishable
    # from an unset one.
    msg = PrePrepareMessage(proposal=Proposal(), proposal_hash=b"")
    assert msg.encode() == b"\x0a\x00"
    decoded = PrePrepareMessage.decode(msg.encode())
    assert decoded.proposal is not None
    assert decoded.certificate is None


def _rich_message() -> IbftMessage:
    prepare = IbftMessage(
        view=View(height=7, round=1),
        sender=b"validator-2",
        signature=b"sig-p",
        type=MessageType.PREPARE,
        prepare_data=PrepareMessage(proposal_hash=b"hash-7"),
    )
    proposal_msg = IbftMessage(
        view=View(height=7, round=1),
        sender=b"validator-1",
        signature=b"sig-pp",
        type=MessageType.PREPREPARE,
        preprepare_data=PrePrepareMessage(
            proposal=Proposal(raw_proposal=b"raw-block", round=1),
            proposal_hash=b"hash-7",
            certificate=RoundChangeCertificate(round_change_messages=[]),
        ),
    )
    return IbftMessage(
        view=View(height=7, round=2),
        sender=b"validator-3",
        signature=b"sig-rc",
        type=MessageType.ROUND_CHANGE,
        round_change_data=RoundChangeMessage(
            last_prepared_proposal=Proposal(raw_proposal=b"raw-block", round=1),
            latest_prepared_certificate=PreparedCertificate(
                proposal_message=proposal_msg,
                prepare_messages=[prepare, prepare],
            ),
        ),
    )


def test_roundtrip_nested():
    msg = _rich_message()
    assert IbftMessage.decode(msg.encode()) == msg


def test_roundtrip_all_types():
    cases = [
        IbftMessage(type=MessageType.PREPARE, prepare_data=PrepareMessage(b"h")),
        IbftMessage(type=MessageType.COMMIT, commit_data=CommitMessage(b"h", b"s")),
        IbftMessage(
            type=MessageType.ROUND_CHANGE, round_change_data=RoundChangeMessage()
        ),
        IbftMessage(
            type=MessageType.PREPREPARE, preprepare_data=PrePrepareMessage()
        ),
    ]
    for msg in cases:
        assert IbftMessage.decode(msg.encode()) == msg


def test_decode_skips_unknown_fields():
    # field 15 varint (tag 0x78), value 1 — must be skipped
    raw = b"\x78\x01" + View(height=9).encode()
    assert View.decode(raw) == View(height=9)


def test_truncated_raises():
    msg = _rich_message().encode()
    with pytest.raises(ValueError):
        IbftMessage.decode(msg[:-1])


@pytest.mark.parametrize("value", [0, 1, 127, 128, 300, 2**32, 2**63, 2**64 - 1])
def test_varint_extremes(value):
    v = View(height=value, round=0)
    assert View.decode(v.encode()).height == value


# -- the bulk encoder of signed payloads (ISSUE 36) ---------------------------
# ``payloads_no_sig`` must return exactly what ``IbftMessage.encode(
# include_signature=False)`` returns, message for message: a plain PREPARE or
# COMMIT built from its template, any other shape handed to ``encode``.  Each
# shape is a case, held to the generic encoder AND to the path it must take.

_H, _S, _A = b"\x11" * 32, b"\x22" * 65, b"\x33" * 20


def _prepare(**kw):
    fields = dict(
        view=View(height=7, round=1),
        sender=_A,
        signature=b"\x44" * 65,
        type=MessageType.PREPARE,
        prepare_data=PrepareMessage(proposal_hash=_H),
    )
    fields.update(kw)
    return IbftMessage(**fields)


def _commit(**kw):
    fields = dict(
        view=View(height=7, round=1),
        sender=_A,
        signature=b"\x44" * 65,
        type=MessageType.COMMIT,
        commit_data=CommitMessage(proposal_hash=_H, committed_seal=_S),
    )
    fields.update(kw)
    return IbftMessage(**fields)


def _round_change():
    inner = _prepare()
    return IbftMessage(
        view=View(height=7, round=2),
        sender=_A,
        type=MessageType.ROUND_CHANGE,
        round_change_data=RoundChangeMessage(
            last_prepared_proposal=Proposal(raw_proposal=b"block", round=1),
            latest_prepared_certificate=PreparedCertificate(
                proposal_message=_preprepare(), prepare_messages=[inner]
            ),
        ),
    )


def _preprepare(certificate=None, raw=b"block"):
    return IbftMessage(
        view=View(height=7, round=1),
        sender=_A,
        type=MessageType.PREPREPARE,
        preprepare_data=PrePrepareMessage(
            proposal=Proposal(raw_proposal=raw, round=1),
            proposal_hash=_H,
            certificate=certificate,
        ),
    )


# name -> (message, built from the template?)
_BULK_CASES = {
    "prepare": (_prepare(), True),
    "commit": (_commit(), True),
    "preprepare": (_preprepare(), False),
    "round_change": (_round_change(), False),
    "prepare_no_view": (_prepare(view=None), False),
    "commit_no_view": (_commit(view=None), False),
    "prepare_height_and_round_0": (_prepare(view=View(0, 0)), True),
    "commit_height_0": (_commit(view=View(0, 3)), True),
    "prepare_round_0": (_prepare(view=View(9, 0)), True),
    "commit_height_2_to_the_32": (_commit(view=View(2**32, 0)), True),
    "prepare_height_2_to_the_63": (_prepare(view=View(2**63 + 5, 2**31)), True),
    "prepare_empty_sender": (_prepare(sender=b""), False),
    "commit_sender_19_bytes": (_commit(sender=b"\x33" * 19), False),
    "prepare_sender_21_bytes": (_prepare(sender=b"\x33" * 21), False),
    "prepare_sender_bytearray": (_prepare(sender=bytearray(_A)), False),
    "prepare_hash_31_bytes": (
        _prepare(prepare_data=PrepareMessage(proposal_hash=_H[:31])),
        False,
    ),
    "prepare_empty_hash": (_prepare(prepare_data=PrepareMessage()), False),
    "commit_hash_33_bytes": (
        _commit(commit_data=CommitMessage(proposal_hash=_H + b"x", committed_seal=_S)),
        False,
    ),
    "commit_seal_64_bytes": (
        _commit(commit_data=CommitMessage(proposal_hash=_H, committed_seal=_S[:64])),
        False,
    ),
    "commit_empty_seal": (
        _commit(commit_data=CommitMessage(proposal_hash=_H)),
        False,
    ),
    "unknown_type": (_prepare(type=7), False),
    "type_as_plain_int": (_commit(type=2), True),
    "prepare_without_its_member": (_prepare(prepare_data=None), False),
    "prepare_type_commit_member": (
        _prepare(prepare_data=None, commit_data=CommitMessage(_H, _S)),
        False,
    ),
    "two_oneof_members": (_prepare(commit_data=CommitMessage(_H, _S)), False),
    "commit_and_preprepare_members": (
        _commit(preprepare_data=PrePrepareMessage(proposal_hash=_H)),
        False,
    ),
    "prepare_and_round_change_members": (
        _prepare(round_change_data=RoundChangeMessage()),
        False,
    ),
    "preprepare_with_certificate": (
        _preprepare(RoundChangeCertificate(round_change_messages=[_round_change()])),
        False,
    ),
    "payload_over_the_device_limit": (_preprepare(raw=bytes(5000)), False),
    "no_signature_yet": (_commit(signature=b""), True),
}


@pytest.mark.parametrize("name", list(_BULK_CASES))
def test_bulk_encoder_is_the_generic_encoder_byte_for_byte(name):
    from go_ibft_tpu.messages.wire import payloads_no_sig

    msg, templated = _BULK_CASES[name]
    want = msg.encode(include_signature=False)
    generic = []
    assert payloads_no_sig([msg], generic) == [want]
    assert generic == ([] if templated else [0])
    assert payloads_no_sig([msg]) == [want]  # the report is optional


def test_bulk_encoder_over_a_mixed_drain_and_over_changing_messages():
    from go_ibft_tpu.messages.wire import payloads_no_sig
    from go_ibft_tpu.verify.batch import MAX_DEVICE_PAYLOAD

    msgs = [m for m, _ in _BULK_CASES.values()]
    # Several views in one call: a prefix each, none leaks into another.
    msgs += [_prepare(view=View(h, r)) for h in (7, 8, 300) for r in (0, 1)]
    generic = []
    got = payloads_no_sig(msgs, generic)
    assert got == [m.encode(include_signature=False) for m in msgs]
    assert generic == [
        i for i, (_, templated) in enumerate(_BULK_CASES.values()) if not templated
    ]
    assert len(_BULK_CASES["payload_over_the_device_limit"][0].payload_no_sig()) > (
        MAX_DEVICE_PAYLOAD
    )
    # Nothing is kept on a message: every field may change between calls
    # (tests and the chaos wrappers do), its view object too.
    msg = _commit()
    first = payloads_no_sig([msg])
    msg.view.height += 1
    msg.sender = b"\x55" * 20
    msg.commit_data.committed_seal = b"\x66" * 65
    second = payloads_no_sig([msg])
    assert first != second == [msg.encode(include_signature=False)]
    msg.commit_data.committed_seal = b"short"
    assert payloads_no_sig([msg]) == [msg.encode(include_signature=False)]
    assert payloads_no_sig([]) == []
    with pytest.raises(ValueError):  # as the generic encoder: no negative varint
        payloads_no_sig([_prepare(view=View(-1, 0))])
