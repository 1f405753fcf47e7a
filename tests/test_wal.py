"""WAL corruption tolerance — the analogue of the reference's
consensus/wal_fuzz.go + wal corrupt-tail handling (consensus/wal.go:231).

The recovery property: whatever bytes end up on disk after a crash or
corruption, replay (a) never raises and (b) yields a PREFIX of the
messages that were written, in order."""

import os
import random
import struct
import zlib

import pytest

from tendermint_tpu.consensus.wal import (
    MAX_MSG_SIZE_BYTES,
    WAL,
    EndHeightMessage,
    WALError,
    WALMessageBlob,
)
from tendermint_tpu.encoding import proto
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE, Vote


def _write_wal(path, n=20):
    wal = WAL(path)
    msgs = []
    for i in range(n):
        if i % 5 == 4:
            m = EndHeightMessage(height=i // 5 + 1)
        else:
            m = WALMessageBlob(kind="vote", payload=b"payload-%d" % i * 3,
                               peer_id="peer%d" % (i % 3))
        wal.write_sync(m, time_ns=1_700_000_000_000_000_000 + i)
        msgs.append(m)
    wal.close()
    return msgs


def _head_file(path):
    names = [n for n in os.listdir(path)]
    assert names
    return os.path.join(path, sorted(names)[-1])


def _replayed(path):
    return [tm.msg for tm, _ in WAL(path).iter_messages()]


def _is_prefix(got, wrote):
    return len(got) <= len(wrote) and got == wrote[: len(got)]


def test_truncation_at_every_byte_is_a_prefix(tmp_path):
    """Crash mid-write: cut the head file at every possible byte offset;
    replay must never raise and always yield a prefix."""
    base = _write_wal(str(tmp_path / "wal"), n=8)
    head = _head_file(str(tmp_path / "wal"))
    full = open(head, "rb").read()
    for cut in range(len(full) + 1):
        d = str(tmp_path / ("cut%d" % cut))
        os.makedirs(d)
        with open(os.path.join(d, os.path.basename(head)), "wb") as f:
            f.write(full[:cut])
        got = _replayed(d)
        assert _is_prefix(got, base), cut
    # the untouched file replays everything
    assert _replayed(str(tmp_path / "wal")) == base


def test_random_bit_flips_yield_prefix(tmp_path):
    """Flip random bytes anywhere in the log; replay stops at (or before)
    the first damaged frame, never raises, never yields altered/reordered
    messages for frames whose CRC still matches."""
    rng = random.Random(0xDEAD)
    base = _write_wal(str(tmp_path / "wal"), n=20)
    head = _head_file(str(tmp_path / "wal"))
    full = bytearray(open(head, "rb").read())
    for trial in range(60):
        data = bytearray(full)
        for _ in range(rng.randint(1, 4)):
            i = rng.randrange(len(data))
            data[i] ^= 1 << rng.randrange(8)
        d = str(tmp_path / ("flip%d" % trial))
        os.makedirs(d)
        with open(os.path.join(d, os.path.basename(head)), "wb") as f:
            f.write(bytes(data))
        got = _replayed(d)
        assert _is_prefix(got, base), trial


def test_giant_length_field_stops_replay(tmp_path):
    """A corrupted length field larger than MAX_MSG_SIZE must terminate
    replay instead of attempting a giant allocation."""
    base = _write_wal(str(tmp_path / "wal"), n=6)
    head = _head_file(str(tmp_path / "wal"))
    data = bytearray(open(head, "rb").read())
    # frame 0 is intact; overwrite frame 1's length with 512 MiB
    _, l0 = struct.unpack_from(">II", data, 0)
    struct.pack_into(">I", data, 8 + l0 + 4, 512 * 1024 * 1024)
    with open(head, "wb") as f:
        f.write(bytes(data))
    got = _replayed(str(tmp_path / "wal"))
    assert got == base[:1]


def test_search_for_end_height_on_corrupt_tail(tmp_path):
    """EndHeight found before the damage still anchors recovery; an
    EndHeight after the damage is unreachable and reports not-found."""
    _write_wal(str(tmp_path / "wal"), n=20)  # EndHeights 1..4
    head = _head_file(str(tmp_path / "wal"))
    data = bytearray(open(head, "rb").read())
    frames = []
    pos = 0
    while pos + 8 <= len(data):
        _, ln = struct.unpack_from(">II", data, pos)
        frames.append(pos)
        pos += 8 + ln
    # damage the 13th frame: EndHeight(2) at frame index 9 stays readable,
    # EndHeight(3) at frame 14 becomes unreachable
    data[frames[12] + 8] ^= 0xFF
    with open(head, "wb") as f:
        f.write(bytes(data))
    wal = WAL(str(tmp_path / "wal"))
    after = wal.search_for_end_height(2)
    assert after is not None and len(after) == 2  # frames 10,11 survive
    assert wal.search_for_end_height(3) is None


def test_append_after_corrupt_tail_recovers_new_writes(tmp_path):
    """Reopening a WAL with a torn tail must truncate the garbage before
    appending (consensus/wal.py _repair; reference:
    consensus/replay.go:73 repairWalFile) — otherwise the new frames land
    after the tear and replay never reaches them."""
    base = _write_wal(str(tmp_path / "wal"), n=5)
    head = _head_file(str(tmp_path / "wal"))
    with open(head, "ab") as f:
        f.write(b"\x00\x01\x02")  # torn partial frame
    wal = WAL(str(tmp_path / "wal"))  # repair on open
    extra = WALMessageBlob(kind="vote", payload=b"post-crash", peer_id="p")
    wal.write_sync(extra, time_ns=1)
    wal.close()
    # old prefix AND the post-crash write both replay
    assert _replayed(str(tmp_path / "wal")) == base + [extra]
    # the damaged original is kept aside for forensics
    assert any(".corrupted." in n for n in os.listdir(str(tmp_path / "wal")))


def test_repair_mid_file_corruption_truncates_to_valid_prefix(tmp_path):
    """Damage in the middle: repair keeps the valid prefix, drops the
    damaged frame AND everything after it (those frames were unreachable
    by replay anyway), and subsequent writes append cleanly."""
    base = _write_wal(str(tmp_path / "wal"), n=8)
    head = _head_file(str(tmp_path / "wal"))
    data = bytearray(open(head, "rb").read())
    data[8] ^= 0xFF  # corrupt frame 0's body -> whole file unreachable
    with open(head, "wb") as f:
        f.write(bytes(data))
    wal = WAL(str(tmp_path / "wal"))
    extra = WALMessageBlob(kind="vote", payload=b"fresh", peer_id="q")
    wal.write_sync(extra, time_ns=2)
    wal.close()
    assert _replayed(str(tmp_path / "wal")) == [extra]
    assert base  # (original messages preserved only in the .corrupted copy)


def test_clean_wal_reopen_does_not_rewrite(tmp_path):
    """Repair must be a no-op on a clean log: no .corrupted files, all
    messages intact after reopen + append."""
    base = _write_wal(str(tmp_path / "wal"), n=5)
    wal = WAL(str(tmp_path / "wal"))
    extra = WALMessageBlob(kind="vote", payload=b"more", peer_id="r")
    wal.write_sync(extra, time_ns=3)
    wal.close()
    assert _replayed(str(tmp_path / "wal")) == base + [extra]
    assert not any(".corrupted." in n
                   for n in os.listdir(str(tmp_path / "wal")))


def test_tear_in_rotated_chunk_repairs_and_retires_later_chunks(tmp_path):
    """Rotation: a tear in an EARLIER (non-head) chunk used to orphan every
    later chunk and all post-crash writes (repair only looked at the head).
    Repair must truncate the torn chunk, retire later chunks (ordering
    across the gap is broken), and make new writes reachable."""
    d = str(tmp_path / "wal")
    wal = WAL(d, head_size_limit=64)  # force rotation every frame or two
    msgs = []
    for i in range(10):
        m = WALMessageBlob(kind="vote", payload=b"chunked-%d" % i * 4,
                           peer_id="p")
        wal.write_sync(m, time_ns=i)
        msgs.append(m)
    wal.close()
    chunks = sorted(n for n in os.listdir(d) if ".corrupted." not in n)
    assert len(chunks) >= 3, chunks  # rotation actually happened
    # tear the tail of the FIRST chunk
    first = os.path.join(d, chunks[0])
    with open(first, "ab") as f:
        f.write(b"\x00\x01")
    wal2 = WAL(d, head_size_limit=64)
    extra = WALMessageBlob(kind="vote", payload=b"post-tear", peer_id="q")
    wal2.write_sync(extra, time_ns=99)
    wal2.close()
    got = [tm.msg for tm, _ in WAL(d, head_size_limit=64).iter_messages()]
    # the first chunk's valid frames survive, later chunks are retired,
    # and the post-tear write is REACHABLE
    assert got and got[-1] == extra
    assert _is_prefix(got[:-1], msgs)
    assert any(".corrupted." in n for n in os.listdir(d))


# ---------------------------------------------------------------------------
# ISSUE 41: a drain's frames built in one pass and written once are the
# frames the per-message writer wrote, byte for byte
# ---------------------------------------------------------------------------

N_VALIDATORS = 5000
T_NS = 1_767_225_600_123_456_789


def _parent_frame(m, time_ns: int) -> bytes:
    """The frame as the per-message writer built it until PR 41, with
    ``proto.Writer``s: the reference the one-pass encoder is held to."""
    if isinstance(m, EndHeightMessage):
        msg = proto.Writer().message(
            1, proto.Writer().varint(1, m.height).out(), always=True).out()
    else:
        inner = (proto.Writer().string(1, m.kind).bytes(2, m.payload)
                 .string(3, m.peer_id).out())
        msg = proto.Writer().message(2, inner, always=True).out()
    body = proto.Writer().varint(1, time_ns).message(2, msg, always=True).out()
    return struct.pack(">II", zlib.crc32(body) & 0xFFFFFFFF, len(body)) + body


# one field of a plain precommit replaced: what the corrupted pass, the fuzz
# surfaces and a chain's first heights deliver
_BLOCK = BlockID(b"\xb1" * 32, PartSetHeader(8, b"\xb2" * 32))
EDGE_VOTES = {
    "plain": {},
    "prevote": {"type": PREVOTE_TYPE},
    "unknown_type": {"type": 0},
    "nil_block": {"block_id": BlockID()},
    "hash_without_parts": {"block_id": BlockID(b"\xb1" * 32)},
    "round_3": {"round": 3},
    "height_0": {"height": 0},
    "index_0": {"validator_index": 0},
    "last_index": {"validator_index": N_VALIDATORS - 1},
    "negative_index": {"validator_index": -1},
    "zero_time": {"timestamp": Time(0, 0)},
    "negative_seconds": {"timestamp": Time(-62135596800, 5)},
    "zero_nanos": {"timestamp": Time(1_767_225_600, 0)},
    "zero_seconds": {"timestamp": Time(0, 999_999_999)},
    "empty_address": {"validator_address": b""},
    "short_address": {"validator_address": b"\x01\x02\x03"},
    "long_address": {"validator_address": b"\xaa" * 300},
    "empty_signature": {"signature": b""},
    "short_signature": {"signature": b"\x07"},
    "long_signature": {"signature": b"\x55" * 20_000},
}


def _vote(**over) -> Vote:
    fields = dict(type=PRECOMMIT_TYPE, height=41, round=0, block_id=_BLOCK,
                  timestamp=Time(1_767_225_600, 987_654_321),
                  validator_address=b"\xad" * 20, validator_index=77,
                  signature=b"\x51" * 64)
    fields.update(over)
    return Vote(**fields)


def _random_drain(n: int, seed: int) -> list[tuple[Vote, str]]:
    """(vote, peer id) a delivery: mostly one height's votes for one block
    from three peers, interleaved, as a drain holds them, with every edge
    above and random departures mixed in."""
    rng = random.Random(f"drain:{n}:{seed}")
    peers = ["", "%040x" % rng.getrandbits(160), "%040x" % rng.getrandbits(160),
             "peerZ"]
    edges = list(EDGE_VOTES.values())
    out = []
    for i in range(n):
        over = {"type": rng.choice((PREVOTE_TYPE, PRECOMMIT_TYPE)),
                "timestamp": Time(1_767_225_600 + rng.randrange(3),
                                  rng.randrange(10**9)),
                "validator_address": rng.randbytes(20),
                "validator_index": rng.randrange(N_VALIDATORS),
                "signature": rng.randbytes(64)}
        if rng.random() < 0.1:
            over["block_id"] = BlockID()          # a vote for nil
        if rng.random() < 0.05:
            over["round"] = rng.randrange(1, 4)
        if rng.random() < 0.05:
            over["height"] = 40                   # a late precommit
        if rng.random() < 0.2:
            over.update(rng.choice(edges))
        out.append((_vote(**over), rng.choice(peers)))
    return out


def _head_bytes(path) -> bytes:
    with open(_head_file(path), "rb") as f:
        return f.read()


def _assert_one_pass_equals_per_message(tmp_path, drain, time_ns=T_NS):
    votes = [v for v, _ in drain]
    payloads = Vote.marshal_many(votes)
    assert payloads == [v.marshal() for v in votes]
    blobs = [WALMessageBlob("vote", v.marshal(), peer) for v, peer in drain]

    one = WAL(str(tmp_path / "one"))
    assert one.write_blobs([("vote", p, peer)
                            for p, (_, peer) in zip(payloads, drain)],
                           time_ns) == 1
    one.close()
    each = WAL(str(tmp_path / "each"))
    for blob in blobs:
        each.write(blob, time_ns)
    each.close()

    written = _head_bytes(str(tmp_path / "one"))
    assert written == _head_bytes(str(tmp_path / "each"))
    assert written == b"".join(_parent_frame(b, time_ns) for b in blobs)
    assert _replayed(str(tmp_path / "one")) == blobs
    assert [Vote.unmarshal(m.payload) for m in _replayed(str(tmp_path / "one"))] \
        == votes


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n", [1, 2, 656, 1024])
def test_a_drain_written_in_one_pass_is_the_file_written_a_message_at_a_time(
        tmp_path, n, seed):
    _assert_one_pass_equals_per_message(tmp_path, _random_drain(n, seed))


@pytest.mark.parametrize("peer", ["", "%040x" % 0xFEED])
@pytest.mark.parametrize("edge", sorted(EDGE_VOTES))
def test_an_odd_vote_alone_or_among_plain_ones_is_written_as_marshal_writes_it(
        tmp_path, edge, peer):
    odd = _vote(**EDGE_VOTES[edge])
    assert Vote.marshal_many([odd]) == [odd.marshal()]
    _assert_one_pass_equals_per_message(
        tmp_path, [(_vote(), "p"), (odd, peer), (_vote(validator_index=3), "p"),
                   (odd, "")])


@pytest.mark.parametrize("time_ns", [0, 1, T_NS])
def test_the_single_writer_is_the_one_pass_encoder_at_n_1(tmp_path, time_ns):
    """Every kind of message through ``write`` / ``write_sync``: the parent's
    bytes, so a log begun before PR 41 is appended to in its own format."""
    msgs = [WALMessageBlob("proposal", b"\x0a\x03abc"),
            WALMessageBlob("timeout", b"", ""),
            WALMessageBlob("", b"x" * 300, "peer"),
            EndHeightMessage(0), EndHeightMessage(41)]
    wal = WAL(str(tmp_path / "wal"))
    for i, m in enumerate(msgs):
        (wal.write_sync if i % 2 else wal.write)(m, time_ns)
    wal.close()
    want = b"".join(_parent_frame(m, time_ns) for m in msgs)
    assert _head_bytes(str(tmp_path / "wal")) == want
    assert _replayed(str(tmp_path / "wal")) == msgs


@pytest.mark.parametrize("path", ["drain", "single"])
def test_a_frame_over_the_size_limit_is_refused_on_both_paths(tmp_path, path):
    wal = WAL(str(tmp_path / "wal"))
    big = b"\x00" * (MAX_MSG_SIZE_BYTES + 1)
    with pytest.raises(WALError, match="too big"):
        if path == "drain":
            wal.write_blobs([("vote", b"ok", "p"), ("vote", big, "p")], T_NS)
        else:
            wal.write(WALMessageBlob("vote", big, "p"), T_NS)
    with pytest.raises(WALError, match="unknown WAL message"):
        wal.write(object(), T_NS)
    wal.close()
    assert _replayed(str(tmp_path / "wal")) == []


def test_a_drain_that_crosses_the_size_limit_rotates_once_at_its_end(tmp_path):
    d = str(tmp_path / "wal")
    wal = WAL(d, head_size_limit=4096)
    first = [("vote", b"a%03d" % i * 8, "p%d" % (i % 3)) for i in range(200)]
    second = [("vote", b"b%03d" % i * 8, "") for i in range(10)]
    wal.write_blobs(first, 1)             # ~12 KB: three limits in one drain
    assert sorted(os.listdir(d)) == ["wal.000000", "wal.000001"]
    assert os.path.getsize(os.path.join(d, "wal.000001")) == 0
    wal.write_blobs(second, 2)            # under the limit: no rotation
    wal.write_sync(EndHeightMessage(1), 3)
    wal.close()
    assert sorted(os.listdir(d)) == ["wal.000000", "wal.000001"]
    got = list(WAL(d, head_size_limit=4096).iter_messages())
    assert [tm.msg for tm, _ in got] == (
        [WALMessageBlob(*b) for b in first + second] + [EndHeightMessage(1)])
    assert [at[0] for _, at in got] == [0] * 200 + [1] * 11
    assert [tm.time_ns for tm, _ in got] == [1] * 200 + [2] * 10 + [3]
