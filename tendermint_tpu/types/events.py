"""Event types + EventBus (reference: types/events.go, types/event_bus.go:33,
libs/pubsub).

The pubsub query language supports the subset the reference's RPC subscribe
uses: "tm.event='NewBlock'" style equality conditions joined by AND
(reference: libs/pubsub/query/query.go).
"""

from __future__ import annotations

import fnmatch
import re
import threading
from dataclasses import dataclass, field

# Event type strings (reference: types/events.go:20-60)
EVENT_NEW_BLOCK = "NewBlock"
EVENT_NEW_BLOCK_HEADER = "NewBlockHeader"
EVENT_NEW_EVIDENCE = "NewEvidence"
EVENT_TX = "Tx"
EVENT_VALIDATOR_SET_UPDATES = "ValidatorSetUpdates"
EVENT_COMPLETE_PROPOSAL = "CompleteProposal"
EVENT_LOCK = "Lock"
EVENT_NEW_ROUND = "NewRound"
EVENT_NEW_ROUND_STEP = "NewRoundStep"
EVENT_POLKA = "Polka"
EVENT_RELOCK = "Relock"
EVENT_TIMEOUT_PROPOSE = "TimeoutPropose"
EVENT_TIMEOUT_WAIT = "TimeoutWait"
EVENT_UNLOCK = "Unlock"
EVENT_VALID_BLOCK = "ValidBlock"
EVENT_VOTE = "Vote"

EVENT_TYPE_KEY = "tm.event"
TX_HASH_KEY = "tx.hash"
TX_HEIGHT_KEY = "tx.height"


@dataclass
class EventDataNewBlock:
    block: object = None
    block_id: object = None
    result_begin_block: object = None
    result_end_block: object = None


@dataclass
class EventDataNewBlockHeader:
    header: object = None
    num_txs: int = 0
    result_begin_block: object = None
    result_end_block: object = None


@dataclass
class EventDataNewEvidence:
    evidence: object = None
    height: int = 0


@dataclass
class EventDataTx:
    height: int = 0
    tx: bytes = b""
    index: int = 0
    result: object = None


@dataclass
class EventDataRoundState:
    height: int = 0
    round: int = 0
    step: str = ""


@dataclass
class EventDataNewRound:
    height: int = 0
    round: int = 0
    step: str = ""
    proposer_address: bytes = b""
    proposer_index: int = -1


@dataclass
class EventDataCompleteProposal:
    height: int = 0
    round: int = 0
    step: str = ""
    block_id: object = None


@dataclass
class EventDataVote:
    vote: object = None


@dataclass
class EventDataValidatorSetUpdates:
    validator_updates: list = field(default_factory=list)


@dataclass
class EventDataString:
    value: str = ""


# value operand: a quoted string or a single bare token (number, hex hash,
# glob pattern) — anything else is a parse error, as in the reference parser.
# Comparison operands may carry the reference grammar's TIME/DATE keyword
# (libs/pubsub/query/query.go DateLayout/TimeLayout).
_VAL = r"'[^']*'|\"[^\"]*\"|[\w.+\-:*?\[\]]+"
_COND_RE = re.compile(
    r"^(?P<key>[\w.\-/]+)\s*"
    rf"(?:(?P<op><=|>=|=|<|>)\s*(?:(?P<tkind>TIME|DATE)\s+)?(?P<val>{_VAL})"
    rf"|\s(?P<word>CONTAINS)\s+(?P<cval>{_VAL})"
    r"|\s(?P<exists>EXISTS))$"
)


# In-band tag for temporal condition operands. \x00 cannot appear in a
# parsed value token and never legitimately starts a quoted operand, so a
# user string like 'TIME up' can never be mistaken for a temporal operand.
_TEMPORAL_TAG = "\x00"


def _parse_operand_time(v: str):
    """RFC3339 (`TIME ...`) or 2006-01-02 (`DATE ...`) -> aware datetime,
    None when unparseable (the reference errors the match out; we treat it
    as no-match). RFC3339 requires a UTC offset: zone-less values return
    None rather than a naive datetime (which would make later comparisons
    raise instead of not matching)."""
    import datetime as _dt

    try:
        if "T" in v:
            t = _dt.datetime.fromisoformat(v.replace("Z", "+00:00"))
            return t if t.tzinfo is not None else None
        d = _dt.date.fromisoformat(v)
        return _dt.datetime(d.year, d.month, d.day, tzinfo=_dt.timezone.utc)
    except ValueError:
        return None


def _split_and(expr: str) -> list[str]:
    """Split on AND outside quotes (a quoted value may contain ' AND ')."""
    parts, buf, quote = [], [], ""
    i = 0
    while i < len(expr):
        c = expr[i]
        if quote:
            if c == quote:
                quote = ""
            buf.append(c)
        elif c in "'\"":
            quote = c
            buf.append(c)
        elif expr.startswith(" AND ", i):
            parts.append("".join(buf))
            buf = []
            i += 4
        else:
            buf.append(c)
        i += 1
    parts.append("".join(buf))
    return parts


class Query:
    """Pubsub query: AND of conditions over event attributes with the
    reference grammar's operators =, <, <=, >, >=, CONTAINS, EXISTS
    (reference: libs/pubsub/query/query.go). Comparison operators apply
    numerically (heights, amounts); `=` additionally supports glob
    patterns on string values (a superset of the reference's exact match).

    conditions: list of (key, op, value) with op in
    {"=", "<", "<=", ">", ">=", "contains", "exists"}; value is None for
    exists."""

    def __init__(self, expr: str):
        self.expr = expr.strip()
        self.conditions: list[tuple[str, str, str | None]] = []
        if self.expr:
            for part in _split_and(self.expr):
                m = _COND_RE.match(part.strip())
                if not m:
                    raise ValueError(f"bad query condition: {part!r}")
                key = m.group("key")
                if m.group("exists"):
                    self.conditions.append((key, "exists", None))
                elif m.group("word"):
                    self.conditions.append(
                        (key, "contains", m.group("cval").strip().strip("'\"")))
                else:
                    val = m.group("val").strip().strip("'\"")
                    if m.group("tkind"):
                        # tag the operand ("\x00TIME <rfc3339>" /
                        # "\x00DATE <date>") — conditions stay 3-tuples for
                        # every consumer, and _cmp dispatches on the tag
                        if _parse_operand_time(val) is None:
                            raise ValueError(f"bad {m.group('tkind')} "
                                             f"operand: {part!r}")
                        val = f"{_TEMPORAL_TAG}{m.group('tkind')} {val}"
                    self.conditions.append((key, m.group("op"), val))

    @staticmethod
    def _cmp(op: str, x: str, v: str) -> bool:
        if v.startswith(_TEMPORAL_TAG):
            # temporal comparison (reference query.go matchValue time case):
            # the event value parses as RFC3339 when it contains 'T', else
            # as a plain date; unparseable values never match
            operand = _parse_operand_time(v.split(" ", 1)[1])
            xt = _parse_operand_time(x)
            if operand is None or xt is None:
                return False
            return {"=": xt == operand, "<": xt < operand,
                    "<=": xt <= operand, ">": xt > operand,
                    ">=": xt >= operand}[op]
        if op == "=":
            return x == v or fnmatch.fnmatchcase(x, v)
        if op == "contains":
            return v in x
        try:
            xn, vn = float(x), float(v)
        except ValueError:
            return False  # comparison operators are numeric otherwise
        return {"<": xn < vn, "<=": xn <= vn,
                ">": xn > vn, ">=": xn >= vn}[op]

    def matches(self, events: dict[str, list[str]]) -> bool:
        for k, op, v in self.conditions:
            vals = events.get(k)
            if vals is None:
                return False
            if op == "exists":
                continue
            if not any(self._cmp(op, x, v) for x in vals):
                return False
        return True

    def __str__(self) -> str:
        return self.expr

    def __eq__(self, other):
        return isinstance(other, Query) and self.expr == other.expr

    def __hash__(self):
        return hash(self.expr)


class Subscription:
    def __init__(self, query: Query, out_capacity: int = 100):
        import collections

        self.query = query
        self.queue: collections.deque = collections.deque(maxlen=out_capacity if out_capacity else None)
        self.event = threading.Event()
        self.cancelled = False
        self.cancel_reason = ""

    def publish(self, msg) -> None:
        self.queue.append(msg)
        self.event.set()

    def next(self, timeout: float | None = None):
        while True:
            if self.queue:
                msg = self.queue.popleft()
                if not self.queue:
                    self.event.clear()
                return msg
            if self.cancelled:
                raise SubscriptionCancelled(self.cancel_reason)
            if not self.event.wait(timeout):
                return None


class SubscriptionCancelled(Exception):
    pass


@dataclass
class PubSubMessage:
    data: object
    events: dict[str, list[str]]


class EventBus:
    """Typed wrapper over a pubsub server (reference: types/event_bus.go)."""

    def __init__(self) -> None:
        self._subs: dict[tuple[str, str], Subscription] = {}
        self._mtx = threading.RLock()

    def subscribe(self, subscriber: str, query: Query | str,
                  out_capacity: int = 100) -> Subscription:
        if isinstance(query, str):
            query = Query(query)
        with self._mtx:
            key = (subscriber, str(query))
            if key in self._subs:
                raise ValueError("already subscribed")
            sub = Subscription(query, out_capacity)
            self._subs[key] = sub
            return sub

    def unsubscribe(self, subscriber: str, query: Query | str) -> None:
        with self._mtx:
            sub = self._subs.pop((subscriber, str(query)), None)
            if sub is None:
                raise ValueError("subscription not found")
            sub.cancelled = True
            sub.event.set()

    def unsubscribe_all(self, subscriber: str) -> None:
        with self._mtx:
            for key in [k for k in self._subs if k[0] == subscriber]:
                sub = self._subs.pop(key)
                sub.cancelled = True
                sub.event.set()

    def num_clients(self) -> int:
        with self._mtx:
            return len({k[0] for k in self._subs})

    def publish(self, event_type: str, data, extra_events: dict[str, list[str]] | None = None) -> int:
        """-> how many subscriptions the message matched and was queued to."""
        if not self._subs:
            return 0  # nobody listens: no dict, no message, no lock
        events = {EVENT_TYPE_KEY: [event_type]}
        if extra_events:
            for k, v in extra_events.items():
                events.setdefault(k, []).extend(v)
        msg = PubSubMessage(data=data, events=events)
        with self._mtx:
            subs = list(self._subs.values())
        queued = 0
        for sub in subs:
            if sub.query.matches(events):
                sub.publish(msg)
                queued += 1
        return queued

    # --- typed publishers (reference: types/event_bus.go:80-300) -----------

    # the publishers of a block's own events return publish()'s count, so
    # the post-commit task can say how many messages a height queued

    def publish_event_new_block(self, data: EventDataNewBlock) -> int:
        extra = _abci_events(data.result_begin_block, data.result_end_block)
        return self.publish(EVENT_NEW_BLOCK, data, extra)

    def publish_event_new_block_header(self, data: EventDataNewBlockHeader) -> int:
        extra = _abci_events(data.result_begin_block, data.result_end_block)
        return self.publish(EVENT_NEW_BLOCK_HEADER, data, extra)

    def publish_event_new_evidence(self, data: EventDataNewEvidence) -> int:
        return self.publish(EVENT_NEW_EVIDENCE, data)

    def publish_event_tx(self, data: EventDataTx) -> int:
        from tendermint_tpu.types.tx import tx_hash

        extra: dict[str, list[str]] = {
            TX_HASH_KEY: [tx_hash(data.tx).hex().upper()],
            TX_HEIGHT_KEY: [str(data.height)],
        }
        if data.result is not None:
            for ev in getattr(data.result, "events", []):
                for attr in ev.attributes:
                    if attr.index:
                        key = f"{ev.type}.{attr.key.decode(errors='replace')}"
                        extra.setdefault(key, []).append(attr.value.decode(errors="replace"))
        return self.publish(EVENT_TX, data, extra)

    def publish_event_vote(self, data: EventDataVote) -> None:
        self.publish(EVENT_VOTE, data)

    def publish_event_valid_block(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_VALID_BLOCK, data)

    def publish_event_new_round_step(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_NEW_ROUND_STEP, data)

    def publish_event_timeout_propose(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_TIMEOUT_PROPOSE, data)

    def publish_event_timeout_wait(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_TIMEOUT_WAIT, data)

    def publish_event_new_round(self, data: EventDataNewRound) -> None:
        self.publish(EVENT_NEW_ROUND, data)

    def publish_event_complete_proposal(self, data: EventDataCompleteProposal) -> None:
        self.publish(EVENT_COMPLETE_PROPOSAL, data)

    def publish_event_polka(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_POLKA, data)

    def publish_event_unlock(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_UNLOCK, data)

    def publish_event_relock(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_RELOCK, data)

    def publish_event_lock(self, data: EventDataRoundState) -> None:
        self.publish(EVENT_LOCK, data)

    def publish_event_validator_set_updates(self, data: EventDataValidatorSetUpdates) -> int:
        return self.publish(EVENT_VALIDATOR_SET_UPDATES, data)


def _abci_events(begin_block, end_block) -> dict[str, list[str]]:
    extra: dict[str, list[str]] = {}
    for res in (begin_block, end_block):
        if res is None:
            continue
        for ev in getattr(res, "events", []):
            for attr in ev.attributes:
                if attr.index:
                    key = f"{ev.type}.{attr.key.decode(errors='replace')}"
                    extra.setdefault(key, []).append(attr.value.decode(errors="replace"))
    return extra
