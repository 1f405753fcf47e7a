"""What crosses the wire when a node catches up from peers, counted from the
chain's block bytes alone: written straight from the reference's
proto/tendermint/blockchain/types.proto (the ``Message`` oneof of channel
0x40), proto/tendermint/p2p/conn.proto (``Packet``, ``PacketMsg``),
p2p/conn/connection.go (a message is cut into packets of at most
``max_packet_msg_payload_size`` bytes, the last one flagged ``eof``),
p2p/conn/secret_connection.go (a write is sealed in frames of 1,024 data
bytes: 4 length bytes + 1,024 + a 16-byte tag = 1,044) and
blockchain/v0/pool.go (a peer that reported ``[base, height]`` is asked only
for heights inside it). Plain Python and this directory's own protobuf
arithmetic; nothing of the program is imported.

  - ``BlockRequest``  = Message{1: {1: height}}
  - ``BlockResponse`` = Message{3: {1: the block's bytes}}
  - ``StatusRequest`` = Message{4: {}}
  - ``StatusResponse`` = Message{5: {1: height, 2: base}} (proto3: a zero left
    out)

A syncing node that applies heights 1..N-1 of a chain of N blocks receives N
``BlockResponse``s on 0x40 (block N carries the commit for N-1), each once
when no request times out and no block is refused, and beside them a few
status messages whose number depends on how long the sync took: one
``StatusResponse`` and one ``StatusRequest`` a peer when the connection is
made, one ``StatusResponse`` for each ``StatusRequest`` the node sent.
``account`` therefore takes what was received, takes the blocks' share off,
and says how many status messages of each kind explain the rest exactly, or
that nothing does.

With ``block_replay`` beside it: the state such a pass must end in
(``ends``), and for a peer that serves a corrupted copy the height and kind
of the refusal, and that the honest copy still replays to the end
(``corrupted``).
"""

from __future__ import annotations

from benchmark.reference import block_replay

CHANNEL = 0x40
PACKET_PAYLOAD = 1024       # config.go DefaultP2PConfig max_packet_msg_payload_size
FRAME_DATA = 1024           # secret_connection.go dataMaxSize
SEALED_FRAME = 4 + FRAME_DATA + 16


def _varint_len(n: int) -> int:
    size = 1
    while n >= 0x80:
        n >>= 7
        size += 1
    return size


def _len_field(body_len: int) -> int:
    """A length-delimited field with a one-byte tag: tag + length + body."""
    return 1 + _varint_len(body_len) + body_len


def _varint_field(value: int) -> int:
    """A varint field with a one-byte tag; proto3 leaves a zero out."""
    return 1 + _varint_len(value) if value else 0


def block_request_len(height: int) -> int:
    return _len_field(_varint_field(height))


def block_response_len(block_len: int) -> int:
    return _len_field(_len_field(block_len))


def status_request_len() -> int:
    return _len_field(0)


def status_response_len(height: int, base: int) -> int:
    return _len_field(_varint_field(height) + _varint_field(base))


def packets(msg_len: int, payload: int = PACKET_PAYLOAD) -> int:
    """Packets a message of this length is cut into: the last carries eof,
    and an empty message still takes one."""
    return max(1, -(-msg_len // payload))


def packet_len(chunk_len: int, eof: bool, channel: int = CHANNEL) -> int:
    """One PacketMsg on the stream: Packet{3: PacketMsg{1: channel, 2: eof,
    3: data}}, length-delimited."""
    msg = (_varint_field(channel) + (2 if eof else 0)
           + (_len_field(chunk_len) if chunk_len else 0))
    return _varint_len(_len_field(msg)) + _len_field(msg)


def frames(write_len: int) -> int:
    """Sealed frames one write of this many bytes takes."""
    return max(1, -(-write_len // FRAME_DATA))


def serves(base: int, height: int, h: int) -> bool:
    """Whether a peer that reported ``[base, height]`` answers a request for
    ``h`` with a block (pool.go: it is asked for nothing else)."""
    return base <= h <= height


def a_pass(raws: list[bytes], payload: int = PACKET_PAYLOAD) -> dict:
    """What the blocks of one clean pass put on channel 0x40 of the syncing
    node, every block once -> ``heights`` [(height, message bytes, packets)],
    ``msgs``, ``bytes``, ``packets``, and ``frames_least``: the sealed frames
    those packets take when each is written on its own (more arrive: status,
    other channels, pings, the handshake)."""
    rows = [(k + 1, block_response_len(len(raw))) for k, raw in enumerate(raws)]
    heights = [(h, n, packets(n, payload)) for h, n in rows]
    least = 0
    for _h, n, count in heights:
        last = n - (count - 1) * payload
        least += (count - 1) * frames(packet_len(payload, False))
        least += frames(packet_len(last, True))
    return {"heights": heights, "msgs": len(heights),
            "bytes": sum(n for _h, n, _c in heights),
            "packets": sum(c for _h, _n, c in heights),
            "frames_least": least}


def account(raws: list[bytes], received: dict, peers: list[tuple[int, int]],
            payload: int = PACKET_PAYLOAD) -> dict | None:
    """``received``: {msgs, packets, bytes} the node counted on 0x40 in one
    clean pass from peers that reported ``peers`` [(base, height)], all the
    same range. -> {status_requests, status_responses} that, with every
    block once, explain the three counts exactly; None where nothing does."""
    want = a_pass(raws, payload)
    ranges = set(peers)
    if len(ranges) != 1:
        return None
    base, height = next(iter(ranges))
    if not all(serves(base, height, h) for h, _n, _c in want["heights"]):
        return None
    extra_msgs = received["msgs"] - want["msgs"]
    extra_bytes = received["bytes"] - want["bytes"]
    if extra_msgs < 0 or received["packets"] - want["packets"] != extra_msgs:
        return None             # a status message is one packet
    req, resp = status_request_len(), status_response_len(height, base)
    # requests * req + responses * resp = extra_bytes, their sum extra_msgs
    if resp == req:
        return None
    responses, rest = divmod(extra_bytes - extra_msgs * req, resp - req)
    requests = extra_msgs - responses
    if rest or responses < 0 or requests < 0:
        return None
    return {"status_requests": requests, "status_responses": responses}


def ends(chain_id: str, genesis, raws: list[bytes], hashes: list[bytes],
         verify_at=()) -> dict:
    """The state a pass over these blocks must end in: ``block_replay``'s
    replay (app hash, last_results_hash, headers, part-set headers, the
    kvstore) with what the wire carried beside it (``wire``)."""
    out = block_replay.replay(chain_id, genesis, raws, hashes, verify_at)
    out["wire"] = a_pass(raws)
    return out


def corrupted(chain_id: str, genesis, clean: list[bytes], bad: list[bytes],
              hashes: list[bytes]) -> dict:
    """One peer serves ``bad``, a copy of ``clean`` with bytes changed at one
    height; another serves ``clean``. -> ``refused`` (height, kind, index)
    where a node that got the bad copy's block refuses it, ``heights``: the
    heights whose bytes differ, and ``completes``: whether the clean copy
    still replays to its last height, which is what the node must do through
    the honest peer once the bad block is refused."""
    got = block_replay.replay(chain_id, genesis, bad, hashes)
    rest = block_replay.replay(chain_id, genesis, clean, hashes)
    return {"refused": got["refused"],
            "data_hash_differs": got.get("data_hash_differs"),
            "heights": [k + 1 for k, (a, b) in enumerate(zip(clean, bad))
                        if a != b],
            "completes": (rest["refused"] is None
                          and rest["applied"] == list(range(1, len(clean)))),
            "app_hash": rest["app_hash"],
            "last_results_hash": rest["last_results_hash"]}
