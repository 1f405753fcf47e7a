"""The address index of ValidatorSet (PR 39): get_by_address and has_address
answer what a scan of ``validators`` answers, whatever the set went through,
and median_time, their heaviest caller, costs one lookup a signature."""

import hashlib
import random
import sys
import threading

import pytest

from tendermint_tpu.crypto import keys
from tendermint_tpu.state.validation import median_time
from tendermint_tpu.types.block import Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet, index_builds
from tendermint_tpu.types.vote import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
)

SIZES = (1, 4, 150, 1000)
POWERS = ("equal", "zipf")


def _validator(i: int, power: int) -> Validator:
    pub = keys.pubkey_from_type_bytes(
        "ed25519", hashlib.sha256(b"index-%d" % i).digest())
    return Validator.new(pub, power)


def _power(kind: str, i: int) -> int:
    return 10 if kind == "equal" else max(1, 1_000_000 // (i + 1))


def _set(n: int, kind: str = "equal") -> ValidatorSet:
    return ValidatorSet([_validator(i, _power(kind, i)) for i in range(n)])


def _scan(vs: ValidatorSet, address):
    """What get_by_address did before the index."""
    for i, v in enumerate(vs.validators):
        if v.address == address:
            return i, v
    return -1, None


def _agrees_with_the_scan(vs: ValidatorSet, addresses) -> None:
    member = vs.validators[0].address if vs.validators else b"\x01" * 20
    odd = [hashlib.sha256(b"nobody").digest()[:20], b"", member[:19],
           member + b"\x00", bytes(20)]
    for address in [*addresses, *odd]:
        i, held = _scan(vs, address)
        got_i, got = vs.get_by_address(address)
        assert got_i == i
        assert vs.has_address(address) is (i >= 0)
        if held is None:
            assert got is None
        else:
            assert got == held and got is not held


def _addresses(vs: ValidatorSet) -> list[bytes]:
    return [v.address for v in vs.validators]


# --- every way a set comes to be -----------------------------------------------


def _built(vs, n, kind):
    return vs


def _copy(vs, n, kind):
    return vs.copy()


def _rotated(vs, n, kind):
    return vs.copy_increment_proposer_priority(3)


def _wire(vs, n, kind):
    return ValidatorSet.unmarshal(vs.marshal())


def _join(vs, n, kind):
    # one lands first in the order, one last
    vs.update_with_change_set([_validator(n, 2_000_000), _validator(n + 1, 1)])
    return vs


def _leave(vs, n, kind):
    gone = vs.validators[0]
    changes = [Validator(gone.address, gone.pub_key, 0)]
    if n == 1:
        changes.append(_validator(n, 5))   # a set may not end up empty
    vs.update_with_change_set(changes)
    return vs


def _reweight(vs, n, kind):
    # the last becomes the first: every position moves
    last = vs.validators[-1]
    vs.update_with_change_set(
        [Validator(last.address, last.pub_key, 3_000_000)])
    return vs


STAGES = (_built, _copy, _rotated, _wire, _join, _leave, _reweight)


@pytest.mark.parametrize("asked_before", (False, True),
                         ids=("cold", "warm"))
@pytest.mark.parametrize("stage", STAGES, ids=lambda f: f.__name__[1:])
@pytest.mark.parametrize("kind", POWERS)
@pytest.mark.parametrize("n", SIZES)
def test_lookups_agree_with_a_scan(n, kind, stage, asked_before):
    vs = _set(n, kind)
    before = _addresses(vs)
    if asked_before:    # the index exists when the stage copies or drops it
        assert vs.has_address(before[-1])
    out = stage(vs, n, kind)
    _agrees_with_the_scan(out, {*before, *_addresses(out)})
    # and what the stage started from still answers for itself
    _agrees_with_the_scan(vs, before[:3])


@pytest.mark.parametrize("n", SIZES)
def test_the_validator_returned_is_a_copy(n):
    vs = _set(n, "zipf")
    address = vs.validators[-1].address
    i, val = vs.get_by_address(address)
    val.voting_power += 7
    val.proposer_priority = 12345
    val.address = b"\xff" * 20
    again_i, again = vs.get_by_address(address)
    assert again_i == i == n - 1
    assert again.voting_power == _scan(vs, address)[1].voting_power
    assert again.voting_power != val.voting_power
    assert again.address == address and again.proposer_priority != 12345


def _appended(vs):
    vs.validators.append(_validator(9000, 1))


def _cut(vs):
    del vs.validators[0]


def _another_list(vs):
    vs.validators = [_validator(9000 + i, 3) for i in range(len(vs.validators))]


def _a_longer_list(vs):
    vs.validators = [_validator(9100, 3)] + list(reversed(vs.validators))


def _emptied(vs):
    vs.validators = []


def _twice(vs):
    vs.validators = vs.validators + [vs.validators[0].copy()]


@pytest.mark.parametrize("shared", (False, True), ids=("own", "shared"))
@pytest.mark.parametrize(
    "change", (_appended, _cut, _another_list, _a_longer_list, _emptied, _twice),
    ids=lambda f: f.__name__[1:])
@pytest.mark.parametrize("n", (4, 150))
def test_a_list_changed_behind_the_set_still_answers_like_a_scan(n, change,
                                                                 shared):
    vs = _set(n)
    before = _addresses(vs)
    assert vs.has_address(before[0])        # the index is there, and now stale
    sibling = vs.copy() if shared else None
    change(vs)
    for _ in range(2):
        _agrees_with_the_scan(vs, {*before, *_addresses(vs)})
    if sibling is not None:                 # the copy kept the true index
        builds = index_builds()
        _agrees_with_the_scan(sibling, before)
        assert index_builds() == builds


@pytest.mark.parametrize("n", SIZES)
def test_a_copy_taken_before_an_update_answers_for_the_old_membership(n):
    vs = _set(n, "zipf")
    assert vs.has_address(vs.validators[0].address)
    old = vs.copy()
    old_addresses = _addresses(old)
    newcomer = _validator(n, 2_000_000)
    gone = vs.validators[-1]
    vs.update_with_change_set(
        [newcomer, Validator(gone.address, gone.pub_key, 0)])
    assert vs.get_by_address(newcomer.address)[0] == 0
    assert not vs.has_address(gone.address)
    assert old.get_by_address(newcomer.address) == (-1, None)
    assert old.get_by_address(gone.address)[0] == n - 1
    _agrees_with_the_scan(old, old_addresses)
    _agrees_with_the_scan(vs, [*old_addresses, newcomer.address])


def test_an_unhashable_address_is_compared_as_the_scan_compared_it():
    vs = _set(4)
    address = vs.validators[2].address
    assert vs.get_by_address(bytearray(address))[0] == 2
    assert vs.has_address(bytearray(address))
    assert vs.get_by_address(bytearray(20)) == (-1, None)
    assert vs.get_by_address(None) == (-1, None)
    assert not vs.has_address("not bytes")


# --- one index a membership, whoever is asked ------------------------------------


@pytest.mark.parametrize("n", (4, 150))
def test_a_chain_of_states_builds_one_index_a_membership(n):
    """update_state's handing-down: next_validators is copied and rotated and
    never asked, validators is a copy of it and is asked at every height."""
    validators = _set(n)
    next_validators = validators.copy_increment_proposer_priority(1)
    proposer = validators.validators[-1].address
    builds = index_builds()
    for _height in range(12):
        assert validators.has_address(proposer)
        rotated = next_validators.copy()
        rotated.increment_proposer_priority(1)
        validators, next_validators = next_validators.copy(), rotated
    assert index_builds() - builds == 1
    # a change of the set, a leaver looked up in the old index: one more,
    # at the first question after it
    newcomer = _validator(n, 7)
    gone = next_validators.validators[0]
    next_validators.update_with_change_set(
        [newcomer, Validator(gone.address, gone.pub_key, 0)])
    assert index_builds() - builds == 1
    for _height in range(6):
        validators = next_validators.copy()
        assert validators.has_address(newcomer.address)
        next_validators = next_validators.copy_increment_proposer_priority(1)
    assert index_builds() - builds == 2


def test_threads_that_read_and_copy_one_set_get_the_scans_answers():
    vs = _set(150)
    want = {v.address: i for i, v in enumerate(vs.validators)}
    absent = hashlib.sha256(b"nobody").digest()[:20]
    wrong: list = []
    start = threading.Barrier(12)

    def reader(k: int) -> None:
        rng = random.Random(k)
        start.wait(timeout=10)
        for _ in range(300):
            s = vs.copy() if rng.random() < 0.3 else vs
            address = rng.choice(list(want))
            if s.get_by_address(address)[0] != want[address]:
                wrong.append(address)
            if s.has_address(absent):
                wrong.append(absent)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=reader, args=(k,)) for k in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []


# --- the block time -------------------------------------------------------------


def _naive_median(commit: Commit, vs: ValidatorSet) -> Time:
    """The weighted median by its definition: the earliest timestamp at
    which the power that signed no later than it passes half the power that
    signed at all. Nothing shared with state/validation.median_time."""
    signed = []
    for cs in commit.signatures:
        if cs.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            continue
        for v in vs.validators:
            if v.address == cs.validator_address:
                signed.append((cs.timestamp, v.voting_power))
                break
    if not signed:
        return Time.zero()
    half = sum(power for _, power in signed) // 2
    instants = [((t.seconds, t.nanos), power) for t, power in signed]
    for instant in sorted({at for at, _ in instants}):
        if sum(power for at, power in instants if at <= instant) > half:
            return Time(*instant)
    raise AssertionError("unreachable: the latest timestamp carries it all")


def _commit(vs: ValidatorSet, rng: random.Random) -> Commit:
    sigs = []
    for v in vs.validators:
        draw = rng.random()
        if draw < 0.12:
            sigs.append(CommitSig.new_absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if draw < 0.24 else BLOCK_ID_FLAG_COMMIT
        # few distinct instants, so that many signatures tie
        stamp = Time(1_700_000_000 + rng.randrange(3),
                     rng.choice((0, 1, 500_000_000, 999_999_999)))
        sigs.append(CommitSig(flag, v.address, stamp, b"s" * 64))
    if sigs:    # one signer the set does not hold, early, with a time of its own
        stranger = hashlib.sha256(
            b"stranger-%d" % rng.randrange(1 << 30)).digest()[:20]
        sigs[rng.randrange(len(sigs))] = CommitSig(
            BLOCK_ID_FLAG_COMMIT, stranger, Time(1_600_000_000, 7), b"s" * 64)
    return Commit(height=5, round=0, block_id=BlockID(), signatures=sigs)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("kind", POWERS)
@pytest.mark.parametrize("n", (1, 4, 33, 150))
def test_median_time_is_the_weighted_median(n, kind, seed):
    vs = _set(n, kind)
    rng = random.Random(1000 * n + seed)
    for _ in range(4):
        commit = _commit(vs, rng)
        assert median_time(commit, vs) == _naive_median(commit, vs)
        assert median_time(commit, vs.copy()) == _naive_median(commit, vs)


def test_median_time_of_nobody_is_the_zero_time():
    vs = _set(4)
    nobody = Commit(height=5, round=0, block_id=BlockID(),
                    signatures=[CommitSig.new_absent() for _ in range(4)])
    assert median_time(nobody, vs) == Time.zero()
    strangers = Commit(height=5, round=0, block_id=BlockID(), signatures=[
        CommitSig(BLOCK_ID_FLAG_COMMIT, bytes([i]) * 20, Time(9, 9), b"s" * 64)
        for i in range(4)])
    assert median_time(strangers, vs) == Time.zero()


class _CountedAddress(bytes):
    """An address that counts how often it is compared."""

    compared = 0

    def __eq__(self, other):
        _CountedAddress.compared += 1
        return bytes.__eq__(self, other)

    __hash__ = bytes.__hash__


def test_median_time_compares_each_signature_a_few_times_not_each_validator():
    """The shape of the cost, with no clock: 2,000 signatures over 2,000
    validators compared two million addresses before the index."""
    n = 2000
    vs = _set(n)
    for v in vs.validators:
        v.address = _CountedAddress(v.address)
    commit = Commit(height=5, round=0, block_id=BlockID(), signatures=[
        CommitSig(BLOCK_ID_FLAG_COMMIT, _CountedAddress(v.address),
                  Time(1_700_000_000, i), b"s" * 64)
        for i, v in enumerate(vs.validators)])
    _CountedAddress.compared = 0
    assert median_time(commit, vs) == Time(1_700_000_000, n // 2)
    assert 0 < _CountedAddress.compared <= 4 * n
