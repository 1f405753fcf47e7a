"""RPC route handlers (reference: rpc/core/*.go, routes at
rpc/core/routes.go:12-48). JSON result shapes follow the reference's
response types (amino-style JSON: hex upper-case hashes, stringified ints).
"""

from __future__ import annotations

import base64
import os
import threading
import time as _time

from tendermint_tpu.abci import types as abci
from tendermint_tpu.types import events as tmevents
from tendermint_tpu.types.tx import tx_hash


class Environment:
    """reference: rpc/core/env.go Environment."""

    def __init__(self, node):
        self.node = node
        self.event_bus = node.event_bus


class ErrOverloaded(Exception):
    """Typed overload verdict from the broadcast_tx admission gate
    (docs/OVERLOAD.md): the node is shedding RPC tx load instead of
    queuing it unboundedly. Clients should back off and retry."""


class _TxAdmissionGate:
    """Max-inflight admission for broadcast_tx_* (no reference analogue —
    the reference lets handler goroutines pile up on the mempool lock).
    One per node; a slot is held for the duration of the CheckTx, the
    part that contends on the mempool + ABCI connection."""

    def __init__(self, limit: int):
        self.limit = limit
        self._inflight = 0
        self._mtx = threading.Lock()

    def acquire(self, board=None) -> None:
        if self.limit <= 0:
            return
        with self._mtx:
            if self._inflight >= self.limit:
                if board is not None:
                    board.count_shed("rpc_tx")
                self._count_shed_metric()
                raise ErrOverloaded(
                    f"node overloaded: {self._inflight} broadcast_tx "
                    f"requests in flight (limit {self.limit}); retry later")
            self._inflight += 1

    @staticmethod
    def _count_shed_metric() -> None:
        """The ingest shed/reject split (docs/INGEST.md): gate sheds land
        in the pre-seeded ingest_txs_total{result="shed"} counter next to
        the batch path's ok/reject tallies."""
        try:
            from tendermint_tpu.utils import metrics as tmmetrics

            m = tmmetrics.GLOBAL_NODE_METRICS
            if m is not None:
                m.ingest_txs.add(1, result="shed")
        except Exception:  # noqa: BLE001 - metrics never block shedding
            pass

    def release(self) -> None:
        if self.limit <= 0:
            return
        with self._mtx:
            self._inflight = max(0, self._inflight - 1)


_GATE_CREATE_MTX = threading.Lock()


def _tx_gate(env) -> _TxAdmissionGate:
    gate = getattr(env.node, "_rpc_tx_gate", None)
    if gate is None:
        with _GATE_CREATE_MTX:
            gate = getattr(env.node, "_rpc_tx_gate", None)
            if gate is None:
                cfg = getattr(getattr(env.node, "config", None), "rpc", None)
                limit = getattr(cfg, "max_broadcast_tx_inflight", 0) if cfg else 0
                gate = _TxAdmissionGate(limit)
                env.node._rpc_tx_gate = gate
    return gate


def _node_scoreboard(env):
    return getattr(getattr(env.node, "switch", None), "scoreboard", None)


def _mempool_submit(env, raw: bytes):
    """Route a broadcast_tx through the micro-batched ingest front door
    (docs/INGEST.md) when the mempool has one: concurrent handler threads
    share batched CheckTx dispatches while each still holds its own
    admission-gate slot. Falls back to plain check_tx for mempool fakes."""
    mp = env.node.mempool
    fn = getattr(mp, "ingest_tx", None)
    if fn is None:
        return mp.check_tx(raw)
    return fn(raw)


def _b64(b: bytes) -> str:
    return base64.b64encode(b or b"").decode()


def _hex(b: bytes) -> str:
    return (b or b"").hex().upper()


def _block_id_json(bid) -> dict:
    return {
        "hash": _hex(bid.hash),
        "parts": {"total": bid.part_set_header.total, "hash": _hex(bid.part_set_header.hash)},
    }


def _header_json(h) -> dict:
    return {
        "version": {"block": str(h.version.block), "app": str(h.version.app)},
        "chain_id": h.chain_id,
        "height": str(h.height),
        "time": str(h.time),
        "last_block_id": _block_id_json(h.last_block_id),
        "last_commit_hash": _hex(h.last_commit_hash),
        "data_hash": _hex(h.data_hash),
        "validators_hash": _hex(h.validators_hash),
        "next_validators_hash": _hex(h.next_validators_hash),
        "consensus_hash": _hex(h.consensus_hash),
        "app_hash": _hex(h.app_hash),
        "last_results_hash": _hex(h.last_results_hash),
        "evidence_hash": _hex(h.evidence_hash),
        "proposer_address": _hex(h.proposer_address),
    }


def _commit_json(c) -> dict:
    if c is None:
        return None
    return {
        "height": str(c.height),
        "round": c.round,
        "block_id": _block_id_json(c.block_id),
        "signatures": [
            {
                "block_id_flag": s.block_id_flag,
                "validator_address": _hex(s.validator_address),
                "timestamp": str(s.timestamp),
                "signature": _b64(s.signature),
            }
            for s in c.signatures
        ],
    }


def _evidence_json(ev) -> dict:
    """reference: types/evidence.go MarshalJSON shapes (subset)."""
    from tendermint_tpu.types.evidence import (
        DuplicateVoteEvidence, LightClientAttackEvidence)

    if isinstance(ev, DuplicateVoteEvidence):
        return {"type": "tendermint/DuplicateVoteEvidence", "value": {
            "vote_a": {"height": str(ev.vote_a.height),
                       "round": ev.vote_a.round,
                       "type": ev.vote_a.type,
                       "validator_address": _hex(ev.vote_a.validator_address),
                       "block_id": _block_id_json(ev.vote_a.block_id)},
            "vote_b": {"height": str(ev.vote_b.height),
                       "round": ev.vote_b.round,
                       "type": ev.vote_b.type,
                       "validator_address": _hex(ev.vote_b.validator_address),
                       "block_id": _block_id_json(ev.vote_b.block_id)},
            "total_voting_power": str(ev.total_voting_power),
            "validator_power": str(ev.validator_power),
            "timestamp": str(ev.timestamp),
        }}
    if isinstance(ev, LightClientAttackEvidence):
        return {"type": "tendermint/LightClientAttackEvidence", "value": {
            "common_height": str(ev.common_height),
            "total_voting_power": str(ev.total_voting_power),
            "timestamp": str(ev.timestamp),
        }}
    return {"type": type(ev).__name__, "value": {}}


def _block_json(b) -> dict:
    return {
        "header": _header_json(b.header),
        "data": {"txs": [_b64(t) for t in b.data.txs]},
        "evidence": {"evidence": [_evidence_json(e) for e in b.evidence]},
        "last_commit": _commit_json(b.last_commit),
    }


def encode_event_data(data) -> dict:
    """Event payloads for WS subscriptions."""
    if isinstance(data, tmevents.EventDataNewBlock):
        return {"type": "tendermint/event/NewBlock",
                "value": {"block": _block_json(data.block)}}
    if isinstance(data, tmevents.EventDataTx):
        return {"type": "tendermint/event/Tx", "value": {
            "TxResult": {"height": str(data.height), "index": data.index,
                         "tx": _b64(data.tx),
                         "result": {"code": data.result.code if data.result else 0}}}}
    if isinstance(data, tmevents.EventDataNewBlockHeader):
        return {"type": "tendermint/event/NewBlockHeader",
                "value": {"header": _header_json(data.header)}}
    if isinstance(data, tmevents.EventDataRoundState):
        return {"type": "tendermint/event/RoundState", "value": {
            "height": str(data.height), "round": data.round, "step": data.step}}
    if isinstance(data, tmevents.EventDataVote):
        return {"type": "tendermint/event/Vote", "value": {"vote": str(data.vote)}}
    return {"type": type(data).__name__, "value": {}}


# --- info routes (reference: rpc/core/routes.go) ----------------------------


def health(env):
    return {}


def status(env):
    node = env.node
    latest_height = node.block_store.height
    meta = node.block_store.load_block_meta(latest_height)
    earliest_meta = node.block_store.load_base_meta()
    pub = node.priv_validator.get_pub_key() if node.priv_validator else None
    return {
        "node_info": {
            "protocol_version": {"p2p": "8", "block": "11", "app": "0"},
            "id": node.node_key.id(),
            "listen_addr": node.transport.node_info.listen_addr,
            "network": node.genesis.chain_id,
            "version": "0.34.24-tpu",
            "channels": _hex(node.transport.node_info.channels),
            "moniker": node.config.base.moniker,
            "other": {
                "tx_index": ("on" if getattr(node, "tx_indexer", None)
                             is not None else "off"),
                "rpc_address": node.config.rpc.laddr,
            },
        },
        "sync_info": {
            "latest_block_hash": _hex(meta.block_id.hash) if meta else "",
            "latest_app_hash": _hex(meta.header.app_hash) if meta else "",
            "latest_block_height": str(latest_height),
            "latest_block_time": str(meta.header.time) if meta else "",
            "earliest_block_hash": (_hex(earliest_meta.block_id.hash)
                                    if earliest_meta else ""),
            "earliest_app_hash": (_hex(earliest_meta.header.app_hash)
                                  if earliest_meta else ""),
            "earliest_block_height": str(node.block_store.base),
            "earliest_block_time": str(earliest_meta.header.time) if earliest_meta else "",
            "catching_up": bool(getattr(node.consensus_reactor, "wait_sync", False)),
        },
        "validator_info": {
            "address": _hex(pub.address()) if pub else "",
            "pub_key": {"type": "tendermint/PubKeyEd25519", "value": _b64(pub.bytes())} if pub else None,
            "voting_power": "0",
        },
    }


def net_info(env):
    sw = env.node.switch
    with sw._peers_mtx:
        peers = list(sw.peers.values())
    return {
        "listening": True,
        "listeners": [env.node.transport.node_info.listen_addr],
        "n_peers": str(len(peers)),
        "peers": [
            {"node_info": {"id": p.id, "moniker": p.node_info.moniker},
             "is_outbound": p.outbound, "remote_ip": p.socket_addr}
            for p in peers
        ],
    }


def genesis(env):
    import json as _json

    return {"genesis": _json.loads(env.node.genesis.to_json())}


def genesis_chunked(env, chunk=0):
    data = env.node.genesis.to_json().encode()
    chunk_size = 16 * 1024 * 1024
    chunks = [data[i:i + chunk_size] for i in range(0, len(data), chunk_size)] or [b""]
    c = int(chunk)
    if c < 0 or c >= len(chunks):
        raise ValueError(f"there are {len(chunks)} chunks, but you requested {c}")
    return {"chunk": str(c), "total": str(len(chunks)), "data": _b64(chunks[c])}


def blockchain(env, minHeight=0, maxHeight=0):
    """reference: rpc/core/blocks.go BlockchainInfo."""
    store = env.node.block_store
    max_h = int(maxHeight) or store.height
    max_h = min(max_h, store.height)
    min_h = max(int(minHeight) or store.base, store.base)
    min_h = max(min_h, max_h - 19)
    metas = []
    for h in range(max_h, min_h - 1, -1):
        m = store.load_block_meta(h)
        if m is not None:
            metas.append({
                "block_id": _block_id_json(m.block_id),
                "block_size": str(m.block_size),
                "header": _header_json(m.header),
                "num_txs": str(m.num_txs),
            })
    return {"last_height": str(store.height), "block_metas": metas}


def block(env, height=0):
    store = env.node.block_store
    h = int(height) or store.height
    b = store.load_block(h)
    m = store.load_block_meta(h)
    if b is None:
        raise ValueError(f"could not find block at height {h}")
    return {"block_id": _block_id_json(m.block_id), "block": _block_json(b)}


def _parse_hash(hash: str) -> bytes:
    """A 32-byte hash arrives as 64 hex chars (URI style) or base64 (JSON
    style); 64 hex chars can't be valid base64 for 32 bytes, so length
    disambiguates."""
    if len(hash) == 64 and all(c in "0123456789abcdefABCDEF" for c in hash):
        return bytes.fromhex(hash)
    return base64.b64decode(hash)


def block_by_hash(env, hash=""):
    b = env.node.block_store.load_block_by_hash(_parse_hash(hash))
    if b is None:
        return {"block_id": None, "block": None}
    m = env.node.block_store.load_block_meta(b.header.height)
    return {"block_id": _block_id_json(m.block_id), "block": _block_json(b)}


def block_search(env, query="", page=1, per_page=30, order_by=""):
    """reference: rpc/core/blocks.go:113 BlockSearch (kv block indexer;
    empty order_by defaults to desc, anything else than asc/desc errors)."""
    indexer = getattr(env.node, "block_indexer", None)
    if indexer is None:
        raise ValueError("block indexing is disabled")
    heights = indexer.search(query)
    if order_by in ("desc", ""):
        heights = list(reversed(heights))
    elif order_by != "asc":
        raise ValueError("expected order_by to be either `asc` or `desc`")
    page, per_page = max(int(page), 1), min(max(int(per_page), 1), 100)
    start = (page - 1) * per_page
    blocks = []
    for h in heights[start:start + per_page]:
        b = env.node.block_store.load_block(h)
        m = env.node.block_store.load_block_meta(h)
        if b is not None and m is not None:
            blocks.append({"block_id": _block_id_json(m.block_id),
                           "block": _block_json(b)})
    return {"blocks": blocks, "total_count": str(len(heights))}


def header(env, height=0):
    """reference: rpc/core/blocks.go:95 Header."""
    store = env.node.block_store
    h = int(height) or store.height
    m = store.load_block_meta(h)
    if m is None:
        raise ValueError(f"could not find header at height {h}")
    return {"header": _header_json(m.header)}


def header_by_hash(env, hash=""):
    """reference: rpc/core/blocks.go:105 HeaderByHash."""
    b = env.node.block_store.load_block_by_hash(_parse_hash(hash))
    if b is None:
        return {"header": None}
    return {"header": _header_json(b.header)}


def block_results(env, height=0):
    h = int(height) or env.node.block_store.height
    resp = env.node.state_store.load_abci_responses(h)
    return {
        "height": str(h),
        "txs_results": [
            {"code": r.code, "data": _b64(r.data), "log": r.log,
             "gas_wanted": str(r.gas_wanted), "gas_used": str(r.gas_used)}
            for r in resp.deliver_txs
        ],
        "begin_block_events": [],
        "end_block_events": [],
        "validator_updates": [],
        "consensus_param_updates": None,
    }


def commit(env, height=0):
    store = env.node.block_store
    h = int(height) or store.height
    m = store.load_block_meta(h)
    if m is None:
        raise ValueError(f"could not find block meta at height {h}")
    c = store.load_block_commit(h) or store.load_seen_commit(h)
    return {
        "signed_header": {"header": _header_json(m.header), "commit": _commit_json(c)},
        "canonical": store.load_block_commit(h) is not None,
    }


def validators(env, height=0, page=1, per_page=30):
    h = int(height) or env.node.block_store.height + 1
    vals = env.node.state_store.load_validators(h)
    page, per_page = max(int(page), 1), min(max(int(per_page), 1), 100)
    start = (page - 1) * per_page
    sel = vals.validators[start:start + per_page]
    return {
        "block_height": str(h),
        "validators": [
            {"address": _hex(v.address),
             "pub_key": {"type": "tendermint/PubKeyEd25519", "value": _b64(v.pub_key.bytes())},
             "voting_power": str(v.voting_power),
             "proposer_priority": str(v.proposer_priority)}
            for v in sel
        ],
        "count": str(len(sel)),
        "total": str(vals.size()),
    }


def consensus_params(env, height=0):
    h = int(height) or env.node.block_store.height + 1
    params = env.node.state_store.load_consensus_params(h)
    return {
        "block_height": str(h),
        "consensus_params": {
            "block": {"max_bytes": str(params.block.max_bytes),
                      "max_gas": str(params.block.max_gas),
                      "time_iota_ms": str(params.block.time_iota_ms)},
            "evidence": {"max_age_num_blocks": str(params.evidence.max_age_num_blocks),
                         "max_age_duration": str(params.evidence.max_age_duration_ns),
                         "max_bytes": str(params.evidence.max_bytes)},
            "validator": {"pub_key_types": list(params.validator.pub_key_types)},
            "version": {"app_version": str(params.version.app_version)},
        },
    }


def consensus_state(env):
    rs = env.node.consensus.rs
    return {"round_state": {
        "height/round/step": f"{rs.height}/{rs.round}/{rs.step}",
        "height": str(rs.height), "round": rs.round, "step": rs.step,
        "start_time": str(rs.start_time),
        "proposal_block_hash": _hex(rs.proposal_block.hash()) if rs.proposal_block else "",
        "locked_block_hash": _hex(rs.locked_block.hash()) if rs.locked_block else "",
        "valid_block_hash": _hex(rs.valid_block.hash()) if rs.valid_block else "",
    }}


def dump_consensus_state(env):
    out = consensus_state(env)
    out["peers"] = [
        {"node_address": p.id,
         "peer_state": {"round_state": {
             "height": str(ps.prs.height), "round": ps.prs.round, "step": ps.prs.step}}}
        for p in env.node.switch.peers.values()
        for ps in [p.get("consensus_peer_state")] if ps is not None
    ]
    return out


def unconfirmed_txs(env, limit=30):
    txs = env.node.mempool.reap_max_txs(min(int(limit), 100))
    return {
        "n_txs": str(len(txs)),
        "total": str(env.node.mempool.size()),
        "total_bytes": str(env.node.mempool.size_bytes()),
        "txs": [_b64(t) for t in txs],
    }


def num_unconfirmed_txs(env):
    return {
        "n_txs": str(env.node.mempool.size()),
        "total": str(env.node.mempool.size()),
        "total_bytes": str(env.node.mempool.size_bytes()),
        "txs": None,
    }


# --- tx routes --------------------------------------------------------------


def _decode_tx_param(tx) -> bytes:
    if isinstance(tx, bytes):
        return tx
    return base64.b64decode(tx)


def broadcast_tx_async(env, tx):
    raw = _decode_tx_param(tx)
    # the admission slot is taken HERE (typed overload error to the
    # caller) and released by the worker thread after CheckTx: async
    # submission must not become an unbounded thread/mempool-queue bomb
    gate = _tx_gate(env)
    gate.acquire(_node_scoreboard(env))
    try:
        threading.Thread(target=_check_tx_quiet, args=(env, raw, gate),
                         daemon=True).start()
    except BaseException:
        # thread spawn failing (fd/thread exhaustion — exactly the
        # overload this gate guards) must not leak the slot forever
        gate.release()
        raise
    return {"code": 0, "data": "", "log": "", "codespace": "", "hash": _hex(tx_hash(raw))}


def _check_tx_quiet(env, raw, gate):
    try:
        _mempool_submit(env, raw)
    except Exception:  # noqa: BLE001
        pass
    finally:
        gate.release()


def broadcast_tx_sync(env, tx):
    raw = _decode_tx_param(tx)
    gate = _tx_gate(env)
    gate.acquire(_node_scoreboard(env))  # ErrOverloaded propagates, typed
    try:
        res = _mempool_submit(env, raw)
        return {"code": res.code, "data": _b64(res.data), "log": res.log,
                "codespace": res.codespace, "hash": _hex(tx_hash(raw))}
    except Exception as e:  # noqa: BLE001
        return {"code": 1, "data": "", "log": str(e), "codespace": "mempool",
                "hash": _hex(tx_hash(raw))}
    finally:
        gate.release()


def broadcast_tx_commit(env, tx):
    """Waits for the tx to be committed (reference: rpc/core/mempool.go:60)."""
    raw = _decode_tx_param(tx)
    # the admission verdict comes FIRST: a shed request must cost nothing —
    # subscribing before the gate would keep the event-bus lock and
    # subscriber map hot under exactly the overload the gate exists to
    # shed. The slot covers only the subscribe + CheckTx (the contended
    # part); holding it through the commit wait would starve the gate on
    # the block interval instead of on actual mempool pressure.
    gate = _tx_gate(env)
    gate.acquire(_node_scoreboard(env))
    q = tmevents.Query(f"{tmevents.EVENT_TYPE_KEY}='{tmevents.EVENT_TX}' AND "
                       f"{tmevents.TX_HASH_KEY}='{_hex(tx_hash(raw))}'")
    subscriber = f"btc-{_hex(tx_hash(raw))[:16]}"
    try:
        sub = env.event_bus.subscribe(subscriber, q)
    except BaseException:
        gate.release()
        raise
    try:
        try:
            check = _mempool_submit(env, raw)
        finally:
            gate.release()
        if not check.is_ok():
            return {"check_tx": {"code": check.code, "log": check.log},
                    "deliver_tx": {}, "hash": _hex(tx_hash(raw)), "height": "0"}
        deadline = _time.monotonic() + env.node.config.rpc.timeout_broadcast_tx_commit_s
        while _time.monotonic() < deadline:
            msg = sub.next(timeout=0.25)
            if msg is not None:
                data = msg.data
                return {
                    "check_tx": {"code": check.code, "log": check.log},
                    "deliver_tx": {"code": data.result.code, "log": data.result.log},
                    "hash": _hex(tx_hash(raw)),
                    "height": str(data.height),
                }
        raise TimeoutError("timed out waiting for tx to be included in a block")
    finally:
        try:
            env.event_bus.unsubscribe_all(subscriber)
        except ValueError:
            pass


def check_tx(env, tx):
    raw = _decode_tx_param(tx)
    res = env.node.proxy_app.mempool.check_tx(abci.RequestCheckTx(tx=raw))
    return {"code": res.code, "data": _b64(res.data), "log": res.log,
            "gas_wanted": str(res.gas_wanted), "gas_used": str(res.gas_used)}


def tx(env, hash="", prove=False):
    """Requires the kv indexer (reference: rpc/core/tx.go)."""
    raw = base64.b64decode(hash) if isinstance(hash, str) else hash
    indexer = getattr(env.node, "tx_indexer", None)
    if indexer is None:
        raise ValueError("transaction indexing is disabled")
    res = indexer.get(raw)
    if res is None:
        raise ValueError(f"tx ({_hex(raw)}) not found")
    if prove:
        # Merkle inclusion proof against the block's data hash (reference:
        # rpc/core/tx.go:47 + types/tx.go Txs.Proof; RFC 6962 tree).
        from tendermint_tpu.types.tx import txs_proof

        block = env.node.block_store.load_block(int(res["height"]))
        if block is None:
            # A proof cannot be constructed for a pruned block; degrading
            # to a proof-less result would read as "verified".
            raise ValueError(
                f"block at height {res['height']} not available for proof")
        idx = int(res["index"])
        txs = block.data.txs
        root, p = txs_proof(list(txs), idx)
        res = dict(res)
        res["proof"] = {
            "root_hash": _hex(root),
            "data": _b64(txs[idx]),
            "proof": {"total": str(p.total), "index": str(p.index),
                      "leaf_hash": _b64(p.leaf_hash),
                      "aunts": [_b64(a) for a in p.aunts]},
        }
    return res


def tx_search(env, query="", prove=False, page=1, per_page=30, order_by="asc"):
    indexer = getattr(env.node, "tx_indexer", None)
    if indexer is None:
        raise ValueError("transaction indexing is disabled")
    results = indexer.search(query)
    page, per_page = max(int(page), 1), min(max(int(per_page), 1), 100)
    start = (page - 1) * per_page
    return {"txs": results[start:start + per_page], "total_count": str(len(results))}


# --- abci routes ------------------------------------------------------------


def abci_query(env, path="", data="", height=0, prove=False):
    raw = bytes.fromhex(data) if isinstance(data, str) else data
    res = env.node.proxy_app.query.query(abci.RequestQuery(data=raw, path=path,
                                               height=int(height), prove=bool(prove)))
    return {"response": {
        "code": res.code, "log": res.log, "info": res.info,
        "index": str(res.index), "key": _b64(res.key), "value": _b64(res.value),
        "height": str(res.height), "codespace": res.codespace,
    }}


def abci_info(env):
    res = env.node.proxy_app.query.info(abci.RequestInfo())
    return {"response": {
        "data": res.data, "version": res.version,
        "app_version": str(res.app_version),
        "last_block_height": str(res.last_block_height),
        "last_block_app_hash": _b64(res.last_block_app_hash),
    }}


def light_block(env, height=0):
    """Hex-marshaled LightBlock for light clients / state sync.

    Not a reference route (the Go light provider assembles a LightBlock from
    /commit + paginated /validators, light/provider/http/http.go:65); one
    binary round-trip replaces 1+N/100 JSON ones. Error messages are part of
    the wire contract: HTTPProvider classifies 'must be less' as
    height-too-high and 'could not find' as not-found."""
    from tendermint_tpu.light.provider import (
        ErrHeightTooHigh,
        ErrLightBlockNotFound,
        NodeProvider,
    )

    h = int(height)
    # Byzantine-primary seam (consensus/misbehavior.py lunatic_proposer,
    # docs/BYZANTINE.md): a maverick node carries a map of fabricated
    # conflicting light blocks and serves THOSE to light clients instead
    # of its honest store — the staged light-client attack the detector +
    # evidence pipeline must catch. Production nodes never grow the
    # attribute, so this is dead code outside adversarial runs.
    fakes = getattr(env.node, "byzantine_light_blocks", None)
    if fakes:
        lb = fakes.get(h or env.node.block_store.height)
        if lb is not None:
            return {"height": str(lb.height), "light_block": lb.marshal().hex()}
    provider = NodeProvider(env.node.genesis.chain_id, env.node.block_store,
                            env.node.state_store)
    try:
        lb = provider.light_block(h)
    except ErrHeightTooHigh as e:
        raise ValueError(
            f"height {h} must be less than or equal to the current blockchain height"
        ) from e
    except ErrLightBlockNotFound as e:
        raise ValueError(f"could not find block: {e}") from e
    return {"height": str(lb.height), "light_block": lb.marshal().hex()}


def _light_gateway(env):
    """The node-local LightGateway (lazily built, cached on the node).

    The primary provider is the node's own self-healing stores; operators
    can cross-check against peer RPC endpoints via TMTPU_GATEWAY_PEERS
    (comma-separated base URLs become witness/spare HTTPProviders). Every
    gateway answer is light-client verified or refused — unlike the raw
    light_block route, which serves whatever the store (or a byzantine
    seam) holds."""
    gw = getattr(env.node, "_light_gateway", None)
    if gw is not None:
        return gw
    from tendermint_tpu.light.gateway import LightGateway, TrustOptions
    from tendermint_tpu.light.provider import HTTPProvider, NodeProvider
    from tendermint_tpu.light.store import DBStore
    from tendermint_tpu.store.db import MemDB

    chain_id = env.node.genesis.chain_id
    primary = NodeProvider(chain_id, env.node.block_store,
                           env.node.state_store)
    providers, names = [primary], ["local"]
    for url in os.environ.get("TMTPU_GATEWAY_PEERS", "").split(","):
        url = url.strip()
        if url:
            providers.append(HTTPProvider(chain_id, url))
            names.append(url)
    base = max(env.node.block_store.base, 1)
    anchor = primary.light_block(base)
    opts = TrustOptions(
        period_s=env.node.config.statesync.trust_period_s,
        height=anchor.height, hash=anchor.hash())
    gw = LightGateway(chain_id, opts, providers, DBStore(MemDB(), chain_id),
                      node=env.node, provider_names=names,
                      logger=getattr(env.node, "logger", None))
    env.node._light_gateway = gw
    return gw


def gateway_light_block(env, height=0):
    """Verified-or-refused light block through the node-local gateway
    (docs/LIGHT.md). height=0 serves the latest verified head."""
    from tendermint_tpu.light.gateway import ErrGatewayDegraded
    from tendermint_tpu.light.provider import (
        ErrHeightTooHigh,
        ErrLightBlockNotFound,
    )

    h = int(height)
    gw = _light_gateway(env)
    try:
        if h == 0:
            lb, verdict = gw.serve_latest()
        else:
            lb, verdict = gw.serve_light_block(h)
    except ErrHeightTooHigh as e:
        raise ValueError(
            f"height {h} must be less than or equal to the current blockchain height"
        ) from e
    except ErrLightBlockNotFound as e:
        raise ValueError(f"could not find block: {e}") from e
    except ErrGatewayDegraded as e:
        raise ValueError(str(e)) from e
    return {"height": str(lb.height), "light_block": lb.marshal().hex(),
            "verdict": verdict}


def gateway_tx(env, hash=""):
    """Tx + Merkle proof verified against a gateway-verified header; a
    quarantined store row refuses instead of serving corrupt bytes."""
    from tendermint_tpu.light.gateway import ErrGatewayDegraded
    from tendermint_tpu.light.provider import ErrLightBlockNotFound

    raw = base64.b64decode(hash) if isinstance(hash, str) else hash
    gw = _light_gateway(env)
    try:
        res = gw.serve_tx(raw)
    except ErrLightBlockNotFound as e:
        raise ValueError(str(e)) from e
    except ErrGatewayDegraded as e:
        raise ValueError(str(e)) from e
    p = res["proof"]
    return {
        "height": str(res["height"]),
        "index": str(res["index"]),
        "tx": _b64(res["tx"]),
        "verdict": res["verdict"],
        "proof": {
            "root_hash": _hex(res["root_hash"]),
            "proof": {"total": str(p.total), "index": str(p.index),
                      "leaf_hash": _b64(p.leaf_hash),
                      "aunts": [_b64(a) for a in p.aunts]},
        },
    }


def gateway_status(env):
    """Gateway introspection: provider scoreboard, cache, verdict counters."""
    return _light_gateway(env).describe()


def broadcast_evidence(env, evidence):
    """reference: rpc/core/evidence.go:17 BroadcastEvidence."""
    from tendermint_tpu.types.evidence import evidence_unmarshal

    ev = evidence_unmarshal(bytes.fromhex(evidence))
    env.node.evidence_pool.add_evidence(ev)
    return {"hash": ev.hash().hex()}


# --- unsafe control routes (reference: rpc/core/routes.go:51
# AddUnsafeRoutes, net.go UnsafeDialSeeds/UnsafeDialPeers,
# mempool.go UnsafeFlushMempool). The reference registers these only when
# config.RPC.Unsafe; here they are always routed but refuse unless
# rpc.unsafe is set — same reachable surface, clearer error. ------------


def _require_unsafe(env) -> None:
    cfg = getattr(getattr(env.node, "config", None), "rpc", None)
    if cfg is None or not cfg.unsafe:
        raise ValueError(
            "unsafe RPC routes are disabled (set rpc.unsafe = true)")


def _validated_addrs(addrs, what: str) -> list:
    """The reference parses every address up front and errors before any
    dialing (net.go UnsafeDialPeers -> NewNetAddressStrings)."""
    if not isinstance(addrs, list) or not addrs:
        raise ValueError(f"no {what} provided (expected a non-empty list)")
    for a in addrs:
        if (not isinstance(a, str) or "@" not in a
                or ":" not in a.rsplit("@", 1)[1]):
            raise ValueError(f"invalid {what[:-1]} address {a!r} "
                             "(expected id@host:port)")
    return addrs


def _dial_async(env, addrs: list, persistent: bool) -> None:
    """Dial in the background — a handler thread must not block for
    N x dial+handshake timeouts (reference dials via DialPeersAsync)."""
    import threading

    def run():
        for a in addrs:
            try:
                env.node.switch.dial_peer(a, persistent=persistent)
            except Exception:  # noqa: BLE001 - one refused dial must not
                # abandon the rest of the list
                continue

    threading.Thread(target=run, name="rpc-dial", daemon=True).start()


def dial_seeds(env, seeds=None):
    _require_unsafe(env)
    _dial_async(env, _validated_addrs(seeds, "seeds"), persistent=False)
    return {"log": "dialing seeds in progress; see /net_info"}


def dial_peers(env, peers=None, persistent=False, unconditional=False,
               private=False):
    _require_unsafe(env)
    if unconditional or private:
        # Reference semantics (net.go:41-66) mark peer ids unconditional/
        # private in the switch+PEX; this build has no such registry, and
        # silently ignoring the flags would mislead callers.
        raise ValueError("unconditional/private peer flags are not supported")
    _dial_async(env, _validated_addrs(peers, "peers"),
                persistent=bool(persistent))
    return {"log": "dialing peers in progress; see /net_info"}


def unsafe_flush_mempool(env):
    _require_unsafe(env)
    env.node.mempool.flush()
    return {}


def unsafe_peers(env, ban=None, unban=None, duration=None):
    """Peer misbehavior scoreboard view + manual ban control
    (utils/peerscore.py; no reference analogue — the overload-resilience
    plane's operator window, docs/OVERLOAD.md).

    With no params, returns scores, active bans (seconds remaining),
    per-offense counts, shed/rate-limit counters, and the threshold
    config. ``ban``/``unban``: a node id to sanction/pardon manually
    (``duration``: ban seconds, default = the configured schedule)."""
    _require_unsafe(env)
    board = _node_scoreboard(env)
    if board is None:
        raise ValueError("node has no peer scoreboard (switch not wired)")
    if ban is not None:
        if not isinstance(ban, str) or not ban:
            raise ValueError("ban must be a non-empty node id")
        board.ban(ban, float(duration) if duration is not None else None)
    if unban is not None:
        if not isinstance(unban, str) or not unban:
            raise ValueError("unban must be a non-empty node id")
        board.unban(unban)
    return board.describe()


def unsafe_nemesis(env, partition=None, heal=False, links=None):
    """Drive this node's peer-scoped link fault plane (utils/nemesis.py;
    no reference analogue — the e2e runner's partition/heal perturbations
    land here, the way runner/perturb.go drives docker network disconnects
    in the reference's containerized e2e).

    ``partition``: list of groups, each a list of node-id prefixes —
    installed symmetrically on every node of a testnet it cuts the links
    between groups. ``heal``: remove the partition (and re-kick persistent
    redials). ``links``: list of "src>dst:action[~p][%prob]" specs."""
    _require_unsafe(env)
    from tendermint_tpu.utils import nemesis

    if heal:
        nemesis.heal()
    if partition is not None:
        if (not isinstance(partition, list)
                or not all(isinstance(g, list) and g for g in partition)):
            raise ValueError("partition must be a list of non-empty groups")
        nemesis.partition(partition)
    if links is not None:
        if not isinstance(links, list):
            raise ValueError("links must be a list of src>dst:action specs")
        for spec in links:
            nemesis.add_link(spec)
    return nemesis.PLANE.describe()


def unsafe_scrub(env, repair=True, timeout=10.0):
    """On-demand storage-integrity scrub (store/scrub.py,
    docs/DURABILITY.md; no reference analogue — the self-healing storage
    plane's operator window).

    Walks the block/state/evidence/tx-index stores, verifies every
    record's CRC envelope + decode, quarantines anything rotten, and —
    with ``repair`` (default true) — synchronously drains the repair
    queue: blocks re-fetched from peers and batch-verified before rewrite,
    state rebuilt from the block store, index rows re-derived. With
    ``repair=false`` every finding is still SCHEDULED (quarantine deletes
    the live row, so dropping the repair would orphan it permanently) but
    drains on the repairer's background worker instead of blocking the
    call. Returns the damage map plus what was healed."""
    _require_unsafe(env)
    repairer = getattr(env.node, "store_repairer", None)
    do_repair = repair in (True, "true", "1", 1)
    report = env.node.scrubber().scrub(
        repairer=repairer, drain=do_repair,
        repair_timeout_s=float(timeout))
    out = report.as_dict()
    if repairer is not None:
        out["pending_repairs"] = [f"{k}:{a!r}" for k, a in repairer.pending()]
        out["needs_statesync"] = repairer.needs_statesync
    return out


def unsafe_trace(env, enable=None, clear=False, dump=False):
    """Flight-recorder control + summary view (utils/trace.py,
    docs/OBSERVABILITY.md; no reference analogue — the reference exposes
    pprof, this build's host-side recorder is span-structured).

    With no params: the tracer's state + per-span-name aggregation, and
    the same aggregation of the process's start-up ring (``startup``: key
    decompression, table builds, jit tracing and compiling; recorded with
    tracing off too), and ``threads``: the process's CPU seconds so far and
    each live thread's by name, with tracing off too (null where the
    platform has no per-thread clock). ``enable``: true/false flips this
    node's tracer live.
    ``clear`` drops the ring. ``dump=true`` adds the raw span lists
    (ring-bounded): ``spans``, and ``startup_spans``."""
    from tendermint_tpu.utils import trace as tmtrace

    _require_unsafe(env)
    tracer = getattr(env.node, "tracer", None)
    if tracer is None:
        raise ValueError("node has no tracer (utils/trace.py not wired)")
    if enable is not None:
        if enable in (True, "true", "1", 1):
            tracer.enable()
        elif enable in (False, "false", "0", 0):
            tracer.disable()
        else:
            raise ValueError("enable must be a boolean")
    if clear in (True, "true", "1", 1):
        tracer.clear()
    out = dict(tracer.describe())
    out["summary"] = tracer.summarize()
    out["startup"] = tmtrace.STARTUP.summarize()
    out["threads"] = tmtrace.thread_cpu_table()
    if dump in (True, "true", "1", 1):
        out["spans"] = [s.as_dict() for s in tracer.dump()]
        out["startup_spans"] = [s.as_dict() for s in tmtrace.STARTUP.dump()]
    return out


def unsafe_timeline(env, height=0):
    """Structured per-height block-lifecycle timeline from the node's
    flight recorder (docs/OBSERVABILITY.md schema): lifecycle marks,
    verify-pipeline phase durations, causal-order verdict. Default
    height: the latest committed block."""
    from tendermint_tpu.utils import trace as tmtrace

    _require_unsafe(env)
    tracer = getattr(env.node, "tracer", None)
    if tracer is None:
        raise ValueError("node has no tracer (utils/trace.py not wired)")
    h = int(height) or env.node.block_store.height
    return tracer.timeline(h)


ROUTES = {
    "health": health,
    "status": status,
    "net_info": net_info,
    "genesis": genesis,
    "genesis_chunked": genesis_chunked,
    "blockchain": blockchain,
    "block": block,
    "block_by_hash": block_by_hash,
    "block_search": block_search,
    "header": header,
    "header_by_hash": header_by_hash,
    "block_results": block_results,
    "commit": commit,
    "light_block": light_block,
    "gateway_light_block": gateway_light_block,
    "gateway_tx": gateway_tx,
    "gateway_status": gateway_status,
    "validators": validators,
    "consensus_params": consensus_params,
    "consensus_state": consensus_state,
    "dump_consensus_state": dump_consensus_state,
    "unconfirmed_txs": unconfirmed_txs,
    "num_unconfirmed_txs": num_unconfirmed_txs,
    "broadcast_tx_async": broadcast_tx_async,
    "broadcast_tx_sync": broadcast_tx_sync,
    "broadcast_tx_commit": broadcast_tx_commit,
    "check_tx": check_tx,
    "tx": tx,
    "tx_search": tx_search,
    "abci_query": abci_query,
    "abci_info": abci_info,
    "broadcast_evidence": broadcast_evidence,
    # unsafe control routes: refuse unless rpc.unsafe (routes.go:51)
    "dial_seeds": dial_seeds,
    "dial_peers": dial_peers,
    "unsafe_flush_mempool": unsafe_flush_mempool,
    "unsafe_nemesis": unsafe_nemesis,
    "unsafe_peers": unsafe_peers,
    "unsafe_scrub": unsafe_scrub,
    "unsafe_trace": unsafe_trace,
    "unsafe_timeline": unsafe_timeline,
}
