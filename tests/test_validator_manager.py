"""Quorum math tables, porting the reference's
core/validator_manager_test.go:11-193 (equal-weight and weighted cases
against floor(2T/3)+1) plus the prepare-quorum special rule."""

import pytest

from go_ibft_tpu.core import StateName, ValidatorManager, VotingPowerError, calculate_quorum
from go_ibft_tpu.messages import (
    IbftMessage,
    MessageType,
    PrepareMessage,
    PrePrepareMessage,
    View,
)
from tests.harness import NullLogger


class _VP:
    def __init__(self, powers):
        self.powers = powers

    def get_voting_powers(self, height):
        return self.powers


def _vm(powers):
    vm = ValidatorManager(_VP(powers), NullLogger())
    vm.init(0)
    return vm


# -- quorum tables (reference validator_manager_test.go) ---------------------


@pytest.mark.parametrize(
    "total,expected",
    [(4, 3), (6, 5), (9, 7), (10, 7), (21, 15), (100, 67), (1, 1), (3, 3)],
)
def test_calculate_quorum(total, expected):
    assert calculate_quorum(total) == expected


def test_equal_weights_4_nodes():
    vm = _vm({bytes([i]): 1 for i in range(4)})
    assert vm.quorum_size == 3
    assert not vm.has_quorum({bytes([0]), bytes([1])})
    assert vm.has_quorum({bytes([0]), bytes([1]), bytes([2])})


def test_equal_weights_6_nodes():
    vm = _vm({bytes([i]): 1 for i in range(6)})
    assert vm.quorum_size == 5
    assert not vm.has_quorum({bytes([i]) for i in range(4)})
    assert vm.has_quorum({bytes([i]) for i in range(5)})


def test_weighted_voting_powers():
    # weighted total 9: quorum = 7
    vm = _vm({b"a": 5, b"b": 3, b"c": 1})
    assert vm.quorum_size == 7
    assert vm.has_quorum({b"a", b"b"})  # 8 >= 7
    assert not vm.has_quorum({b"a", b"c"})  # 6 < 7
    assert not vm.has_quorum({b"b", b"c"})  # 4 < 7


def test_unknown_senders_contribute_zero():
    vm = _vm({b"a": 2, b"b": 2})
    assert not vm.has_quorum({b"ghost", b"phantom"})
    assert vm.has_quorum({b"a", b"b", b"ghost"})


def _reduce(powers, how):
    """``senders -> reached`` by the engine's manager or by the verify
    plane's one host-side reduction (the ladder's early-exit fallback)."""
    if how == "has_quorum":
        return _vm(powers).has_quorum
    from go_ibft_tpu.verify.batch import host_quorum_reached

    return lambda senders: host_quorum_reached(lambda h: powers, senders, 0, None)


@pytest.mark.parametrize("how", ["has_quorum", "host_quorum_reached"])
def test_duplicate_sender_counts_power_once(how):
    """A batch may carry one validator's message many times (a mask lane
    each): its power counts once, in Python ints, at any scale."""
    for unit in (1, 10**24):
        reached = _reduce({bytes([i]): unit for i in range(4)}, how)  # quorum 3
        a, b, c = bytes([0]), bytes([1]), bytes([2])
        assert not reached([a, a, a, b])  # distinct power 2 < 3
        assert reached([a, a, a, b, c])


def test_host_quorum_reached_threshold_override_and_zero_edge():
    """The explicit threshold (the prepare phase's ``quorum - proposer``
    credit) replaces the height's quorum; a threshold <= 0 is reached by
    an empty set."""
    from go_ibft_tpu.verify.batch import host_quorum_reached

    powers = {bytes([i]): 1 for i in range(4)}
    src = lambda height: powers  # noqa: E731
    three = [bytes([i]) for i in range(3)]
    assert host_quorum_reached(src, three, 0, None)
    assert not host_quorum_reached(src, three, 0, 4)
    assert host_quorum_reached(src, three[:2], 0, 2)
    assert host_quorum_reached(src, [], 0, 0)
    assert not host_quorum_reached(src, [], 0, None)


def test_zero_total_voting_power_rejected():
    vm = ValidatorManager(_VP({}), NullLogger())
    with pytest.raises(VotingPowerError):
        vm.init(0)
    vm2 = ValidatorManager(_VP({b"a": 0}), NullLogger())
    with pytest.raises(VotingPowerError):
        vm2.init(0)


def test_has_quorum_before_init_false():
    vm = ValidatorManager(_VP({b"a": 1}), NullLogger())
    assert not vm.has_quorum({b"a"})


def test_big_int_voting_powers():
    # parity with Go big.Int: voting powers beyond 2^64
    big = 2**200
    vm = _vm({b"a": big, b"b": big, b"c": big, b"d": 1})
    assert vm.quorum_size == (2 * (3 * big + 1)) // 3 + 1
    assert vm.has_quorum({b"a", b"b", b"c"})
    # 2·big + 1 == quorum exactly -> has quorum (boundary)
    assert vm.has_quorum({b"a", b"b", b"d"})
    # big + 1 < quorum
    assert not vm.has_quorum({b"a", b"d"})


# -- prepare quorum rule (reference validator_manager.go:99-127) -------------


def _prepare_msg(sender):
    return IbftMessage(
        view=View(height=0, round=0),
        sender=sender,
        type=MessageType.PREPARE,
        prepare_data=PrepareMessage(proposal_hash=b"h"),
    )


def _proposal_msg(sender):
    return IbftMessage(
        view=View(height=0, round=0),
        sender=sender,
        type=MessageType.PREPREPARE,
        preprepare_data=PrePrepareMessage(proposal_hash=b"h"),
    )


def test_prepare_quorum_counts_proposer():
    vm = _vm({bytes([i]): 1 for i in range(4)})  # quorum 3
    proposal = _proposal_msg(bytes([0]))
    # proposer + 2 distinct preparers = 3 senders -> quorum
    msgs = [_prepare_msg(bytes([1])), _prepare_msg(bytes([2]))]
    assert vm.has_prepare_quorum(StateName.PREPARE, proposal, msgs)
    # proposer + 1 preparer = 2 < 3
    assert not vm.has_prepare_quorum(StateName.PREPARE, proposal, msgs[:1])


def test_prepare_quorum_proposer_must_not_prepare():
    vm = _vm({bytes([i]): 1 for i in range(4)})
    proposal = _proposal_msg(bytes([0]))
    msgs = [
        _prepare_msg(bytes([0])),  # proposer prepping: protocol violation
        _prepare_msg(bytes([1])),
        _prepare_msg(bytes([2])),
    ]
    assert not vm.has_prepare_quorum(StateName.PREPARE, proposal, msgs)


def test_prepare_quorum_no_proposal():
    vm = _vm({bytes([i]): 1 for i in range(4)})
    msgs = [_prepare_msg(bytes([i])) for i in range(4)]
    assert not vm.has_prepare_quorum(StateName.PREPARE, None, msgs)
    assert not vm.has_prepare_quorum(StateName.NEW_ROUND, None, msgs)
