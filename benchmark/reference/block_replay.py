"""A chain whose blocks carry transactions, replayed from its block bytes:
what a block's body commits a node to, written straight from the reference's
types/tx.go (Txs.Hash), types/part_set.go (NewPartSetFromData),
types/results.go (ABCIResults.Hash), abci/example/kvstore/kvstore.go and
state/validation.go, over plain Python: ``hashlib`` and this directory's own
protobuf reader (``valset_replay.py``); nothing of the program is imported.

  - ``data_hash`` of a header is the RFC 6962 root over the SHA-256 of each
    transaction of the block, in order;
  - a block's part set is its bytes cut every 65,536; the part-set header is
    the count and the RFC 6962 root over the parts;
  - the application is the reference's kvstore: a transaction ``k=v`` sets
    ``k`` to ``v`` (one without ``=`` sets itself to itself), answers code 0
    with no data and no gas, and the app hash after a block is the count of
    transactions delivered so far, eight bytes big-endian;
  - ``last_results_hash`` of header H+1 is the RFC 6962 root over the
    deterministic encodings of block H's ``ResponseDeliverTx`` (code, data,
    gas wanted, gas used: proto3, so a kvstore answer encodes to nothing),
    and the hash of no bytes for a block without transactions; header 1
    names none;
  - header H+1's ``app_hash`` is the app hash after block H;
  - the commit for block H, carried by block H+1, signs a BlockID: block H's
    hash **and the header of its part set**. Bytes that are not the bytes
    the validators signed therefore fail there first, whatever else of them
    is wrong (``commit_block_id``), as types/validator_set.go
    VerifyCommitLight compares the whole BlockID before any signature.

``replay`` walks the chain with ``valset_replay.replay`` beside it (the set,
here static, its hashes, the commits and their light prefixes) and refuses at
the first height either of the two refuses; at one height the commit is
looked at first, as a syncing node looks at it first.
"""

from __future__ import annotations

import hashlib
import struct

from benchmark.reference import valset_replay
from benchmark.reference.light_sync import _varint, merkle_root
from benchmark.reference.valset_replay import _fields, _int64, _one

PART_SIZE = 65536


def parse_body(raw: bytes) -> dict:
    """Block{header=1, data=2} -> the header's height and the three hashes a
    body answers for, and the transactions."""
    header = _one(raw, 1)
    return {"height": _int64(_one(header, 3, 0)),
            "data_hash": _one(header, 7),
            "app_hash": _one(header, 11),
            "last_results_hash": _one(header, 12),
            "txs": [v for f, v in _fields(_one(raw, 2)) if f == 1]}


def data_hash(txs: list[bytes]) -> bytes:
    """types/tx.go Txs.Hash: the tree's leaves are the transactions' hashes."""
    return merkle_root([hashlib.sha256(tx).digest() for tx in txs])


def parts(raw: bytes, part_size: int = PART_SIZE) -> list[bytes]:
    return [raw[i:i + part_size] for i in range(0, len(raw), part_size)] or [b""]


def part_set_header(raw: bytes, part_size: int = PART_SIZE) -> tuple[int, bytes]:
    """(total, hash) of the block's part set."""
    chunks = parts(raw, part_size)
    return len(chunks), merkle_root(chunks)


def signed_part_set_header(carrier: bytes) -> tuple[int, bytes] | None:
    """(total, hash) of the part set that the LastCommit of this block
    signs: Block{last_commit=4}.Commit{block_id=3}.BlockID{part_set_header=2}
    .PartSetHeader{total=1, hash=2}. None for a block without a commit."""
    commit = _one(carrier, 4, None)
    if commit is None:
        return None
    header = _one(_one(commit, 3), 2)
    return _one(header, 1, 0), _one(header, 2)


def deliver(store: dict, tx: bytes) -> tuple[int, bytes, int, int]:
    """kvstore DeliverTx -> (code, data, gas wanted, gas used)."""
    key, sep, value = tx.partition(b"=")
    store[key] = value if sep else tx
    return 0, b"", 0, 0


def result_bytes(code: int, data: bytes, gas_wanted: int, gas_used: int) -> bytes:
    """types/results.go deterministicResponseDeliverTx, proto3: fields 1, 2,
    5 and 6, a zero value left out."""
    out = b""
    if code:
        out += b"\x08" + _varint(code)
    if data:
        out += b"\x12" + _varint(len(data)) + data
    if gas_wanted:
        out += b"\x28" + _varint(gas_wanted & (1 << 64) - 1)
    if gas_used:
        out += b"\x30" + _varint(gas_used & (1 << 64) - 1)
    return out


def results_hash(results: list[tuple]) -> bytes:
    return merkle_root([result_bytes(*r) for r in results])


def app_hash(delivered: int) -> bytes:
    return struct.pack(">Q", delivered)


def replay(chain_id: str, genesis, raws: list[bytes], hashes: list[bytes],
           verify_at=()) -> dict:
    """Apply blocks 1..N-1 of ``raws`` (block N only carries the commit for
    N-1). ``genesis``: [(key, power)]; ``hashes[k]``: the hash of ``raws[k]``;
    ``verify_at``: heights whose light prefix is verified signature by
    signature.

    -> ``applied`` (heights), ``refused`` (None or (height, kind, index)),
    ``store`` (the kvstore as a dict), ``delivered``, ``app_hash`` and
    ``last_results_hash`` after the last applied height, ``headers`` {height:
    (data_hash, last_results_hash, app_hash) as computed here, which the
    header's own equalled}, ``part_set_headers`` {height: (total, hash)},
    ``txs`` {height: count}, ``prefixes`` {height: slots of its light
    prefix}; with a ``commit_block_id`` refusal, ``data_hash_differs``: whether
    the refused bytes also miss their own header's ``data_hash``."""
    sets = valset_replay.replay(chain_id, genesis, raws, hashes, verify_at)
    stop = sets["refused"][0] if sets["refused"] else len(raws)
    out = {"applied": [], "refused": None, "store": {}, "delivered": 0,
           "app_hash": b"", "last_results_hash": b"", "headers": {},
           "part_set_headers": {}, "txs": {}, "prefixes": sets["prefixes"],
           "validators": sets["validators"]}
    for k, raw in enumerate(raws[:-1]):
        h = k + 1
        body = parse_body(raw)
        part_set = part_set_header(raw)
        if signed_part_set_header(raws[k + 1]) != part_set:
            out["refused"] = (h, "commit_block_id", None)
            out["data_hash_differs"] = (data_hash(body["txs"])
                                        != body["data_hash"])
            break
        if h >= stop:
            out["refused"] = sets["refused"]
            break
        want = (data_hash(body["txs"]), out["last_results_hash"],
                out["app_hash"])
        for name, mine in zip(("data_hash", "last_results_hash", "app_hash"),
                              want):
            if body[name] != mine:
                out["refused"] = (h, name, None)
                break
        if body["height"] != h:
            out["refused"] = (h, "height", None)
        if out["refused"]:
            break
        results = [deliver(out["store"], tx) for tx in body["txs"]]
        out["delivered"] += len(results)
        out["app_hash"] = app_hash(out["delivered"])
        out["last_results_hash"] = results_hash(results)
        out["headers"][h] = want
        out["part_set_headers"][h] = part_set
        out["txs"][h] = len(results)
        out["applied"].append(h)
    return out
