"""The wire encoding of the repeated messages, field by field with
proto.Writer: the rules types/ encoded by before Commit.marshal and
ValidatorSet.marshal went to one pass, kept here as the reference that
tests/test_types.py and tests/test_light.py hold the encoders to."""

from tendermint_tpu.encoding import proto


def loop_varint(n: int) -> bytes:
    """encode_varint as a loop per byte (the codec's before the tables)."""
    if n < 0:
        n += 1 << 64
    if n < 0:
        raise ValueError("uvarint cannot be negative")
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def time_body(t) -> bytes:
    return proto.Writer().varint(1, t.seconds).varint(2, t.nanos).out()


def commit_sig(cs) -> bytes:
    return (
        proto.Writer()
        .varint(1, cs.block_id_flag)
        .bytes(2, cs.validator_address)
        .message(3, time_body(cs.timestamp), always=True)
        .bytes(4, cs.signature)
        .out()
    )


def commit(c) -> bytes:
    w = (
        proto.Writer()
        .varint(1, c.height)
        .varint(2, c.round)
        .message(3, c.block_id.marshal(), always=True)
    )
    for cs in c.signatures:
        w.message(4, commit_sig(cs), always=True)
    return w.out()


def pubkey(pub) -> bytes:
    field = {"ed25519": 1, "secp256k1": 2, "sr25519": 3}[pub.type]
    return proto.Writer().bytes(field, pub.bytes()).out()


def validator(v) -> bytes:
    return (
        proto.Writer()
        .bytes(1, v.address)
        .message(2, pubkey(v.pub_key), always=True)
        .varint(3, v.voting_power)
        .varint(4, v.proposer_priority)
        .out()
    )


def validator_set(vs) -> bytes:
    w = proto.Writer()
    for v in vs.validators:
        w.message(1, validator(v))
    if vs.proposer is not None:
        w.message(2, validator(vs.proposer))
    w.varint(3, vs.total_voting_power())
    return w.out()


def light_block(lb) -> bytes:
    sh = (
        proto.Writer()
        .message(1, lb.signed_header.header.marshal(), always=True)
        .message(2, commit(lb.signed_header.commit), always=True)
        .out()
    )
    return (
        proto.Writer()
        .message(1, sh, always=True)
        .message(2, validator_set(lb.validator_set), always=True)
        .out()
    )
