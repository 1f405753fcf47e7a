"""State store: persists State + per-height validator/params history + ABCI
responses (reference: state/store.go:100-661).

Layout:
  stateKey                    -> full State
  validatorsKey:<height>      -> ValidatorsInfo {set | last_height_changed}
  consensusParamsKey:<height> -> ConsensusParamsInfo {params | last_height_changed}
  abciResponsesKey:<height>   -> serialized DeliverTx responses + EndBlock

The validator history trick mirrors the reference: heights where nothing
changed store only a back-pointer to last_height_changed
(state/store.go:483-560), so lookups may take one indirection.
"""

from __future__ import annotations

from tendermint_tpu.abci.types import ResponseDeliverTx
from tendermint_tpu.encoding import proto
from tendermint_tpu.state.state import State
from tendermint_tpu.store import envelope
from tendermint_tpu.store.db import DB
from tendermint_tpu.types.block import Consensus
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.params import ConsensusParams
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.utils import faults
from tendermint_tpu.utils import trace as _trace

_STATE_KEY = b"stateKey"
VALSET_CHECK_INTERVAL = 100000  # reference: state/store.go valSetCheckpointInterval


def _val_key(h: int) -> bytes:
    return b"validatorsKey:%020d" % h


def _params_key(h: int) -> bytes:
    return b"consensusParamsKey:%020d" % h


def _abci_key(h: int) -> bytes:
    return b"abciResponsesKey:%020d" % h


class StateStoreError(Exception):
    pass


class ErrNoValSetForHeight(StateStoreError):
    def __init__(self, height: int):
        super().__init__(f"could not find validator set for height #{height}")


def _marshal_state(s: State) -> bytes:
    w = proto.Writer()
    w.message(1, s.version.marshal(), always=True)
    w.string(2, s.chain_id)
    w.varint(3, s.last_block_height)
    w.message(4, s.last_block_id.marshal(), always=True)
    w.message(5, s.last_block_time.marshal(), always=True)
    w.message(6, s.next_validators.marshal() if s.next_validators else b"", always=True)
    w.message(7, s.validators.marshal() if s.validators else b"", always=True)
    w.message(8, s.last_validators.marshal() if s.last_validators else b"", always=True)
    w.varint(9, s.last_height_validators_changed)
    w.message(10, s.consensus_params.marshal(), always=True)
    w.varint(11, s.last_height_consensus_params_changed)
    w.bytes(12, s.last_results_hash)
    w.bytes(13, s.app_hash)
    w.varint(14, s.initial_height)
    return w.out()


def _unmarshal_state(buf: bytes) -> State:
    f = proto.fields(buf)
    return State(
        version=Consensus.unmarshal(f.get(1, [b""])[-1]),
        chain_id=f.get(2, [b""])[-1].decode() if 2 in f else "",
        last_block_height=proto.as_sint64(f.get(3, [0])[-1]),
        last_block_id=BlockID.unmarshal(f.get(4, [b""])[-1]),
        last_block_time=Time.unmarshal(f.get(5, [b""])[-1]),
        next_validators=ValidatorSet.unmarshal(f.get(6, [b""])[-1]),
        validators=ValidatorSet.unmarshal(f.get(7, [b""])[-1]),
        last_validators=ValidatorSet.unmarshal(f.get(8, [b""])[-1]),
        last_height_validators_changed=proto.as_sint64(f.get(9, [0])[-1]),
        consensus_params=ConsensusParams.unmarshal(f.get(10, [b""])[-1]),
        last_height_consensus_params_changed=proto.as_sint64(f.get(11, [0])[-1]),
        last_results_hash=f.get(12, [b""])[-1],
        app_hash=f.get(13, [b""])[-1],
        initial_height=proto.as_sint64(f.get(14, [1])[-1]) or 1,
    )


class ABCIResponses:
    """reference: state/store.go:60-75 (tmstate.ABCIResponses)."""

    def __init__(self, deliver_txs: list[ResponseDeliverTx] | None = None,
                 end_block=None, begin_block=None):
        self.deliver_txs = deliver_txs or []
        self.end_block = end_block
        self.begin_block = begin_block

    def marshal(self) -> bytes:
        w = proto.Writer()
        for r in self.deliver_txs:
            w.message(1, r.marshal(), always=True)
        return w.out()

    @staticmethod
    def unmarshal(buf: bytes) -> "ABCIResponses":
        f = proto.fields(buf)
        return ABCIResponses(
            deliver_txs=[ResponseDeliverTx.unmarshal(b) for b in f.get(1, [])]
        )


LOAD_SITE = "store.state.load"


class StateStore:
    def __init__(self, db: DB):
        self._db = db
        # repair hook: wired by the node to its StoreRepairer so every
        # integrity detection quarantines + schedules (docs/DURABILITY.md)
        self.on_corruption = None

    def _load_checked(self, key: bytes, fn):
        """DB get -> fault site -> envelope unwrap -> guarded decode: the
        checked read path every load below routes through. Corruption
        raises the typed CorruptedStoreError naming the key, never a bare
        proto/struct error."""
        raw = faults.mutate_value(LOAD_SITE, self._db.get(key))
        if raw is None:
            return None
        return envelope.decode(raw, "state", key, fn,
                               on_corruption=self.on_corruption)

    def _set(self, key: bytes, payload: bytes) -> None:
        self._db.set(key, envelope.wrap(payload))

    # --- state -------------------------------------------------------------

    def load(self) -> State:
        st = self._load_checked(_STATE_KEY, _unmarshal_state)
        return State() if st is None else st

    def save(self, state: State) -> None:
        """Persist state + index validator/params history (reference:
        state/store.go:174-205). Traced as span state.save."""
        if not _trace.ENABLED:
            self._save(state)
            return
        tr = _trace.current()
        with tr.span("state.save", height=state.last_block_height,
                     validators=state.validators.size()
                     if state.validators else 0):
            tr.annotate(bytes=self._save(state))

    def _save(self, state: State) -> int:
        """-> the bytes of the State's own encoding."""
        next_height = state.last_block_height + 1
        if next_height == 1:
            next_height = state.initial_height
            self._save_validators(next_height, state.last_height_validators_changed,
                                  state.validators)
        self._save_validators(next_height + 1, state.last_height_validators_changed,
                              state.next_validators)
        self._save_params(next_height, state.last_height_consensus_params_changed,
                          state.consensus_params)
        # crash between the history rows above and the state key below is
        # the interesting torn-state case replay must absorb
        faults.fire("store.state.save")
        raw = _marshal_state(state)
        self._set(_STATE_KEY, raw)
        return len(raw)

    def bootstrap(self, state: State) -> None:
        """reference: state/store.go:207-241."""
        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height
        if height > 1 and state.last_validators and not state.last_validators.is_nil_or_empty():
            self._save_validators(height - 1, height - 1, state.last_validators)
        self._save_validators(height, height, state.validators)
        self._save_validators(height + 1, height + 1, state.next_validators)
        self._save_params(height, state.last_height_consensus_params_changed,
                          state.consensus_params)
        self._set(_STATE_KEY, _marshal_state(state))

    # --- validator history -------------------------------------------------

    def _save_validators(self, height: int, last_changed: int, vals: ValidatorSet) -> None:
        if vals is None:
            return
        if last_changed == height or height % VALSET_CHECK_INTERVAL == 0:
            body = proto.Writer().message(1, vals.marshal(), always=True).varint(2, last_changed).out()
        else:
            body = proto.Writer().varint(2, last_changed).out()
        self._set(_val_key(height), body)

    def load_validators(self, height: int) -> ValidatorSet:
        """reference: state/store.go:483-530 (with back-pointer chase)."""
        f = self._load_checked(_val_key(height), proto.fields)
        if f is None:
            raise ErrNoValSetForHeight(height)
        if 1 in f:
            return ValidatorSet.unmarshal(f[1][-1])
        last_changed = proto.as_sint64(f.get(2, [0])[-1])
        f2 = self._load_checked(_val_key(last_changed), proto.fields)
        if f2 is None:
            raise ErrNoValSetForHeight(height)
        if 1 not in f2:
            raise StateStoreError(
                f"validator checkpoint at height {last_changed} is itself a pointer"
            )
        return ValidatorSet.unmarshal(f2[1][-1])

    def validators_last_changed(self, height: int) -> int | None:
        """The back-pointer (or self height) of one validator-history row;
        None when the row is missing. The state repairer uses intact
        NEIGHBOR rows to re-derive a quarantined pointer row
        (store/repair.py)."""
        f = self._load_checked(_val_key(height), proto.fields)
        if f is None:
            return None
        return height if 1 in f else proto.as_sint64(f.get(2, [0])[-1])

    def rewrite_validators(self, height: int, last_changed: int,
                           vals: ValidatorSet | None) -> None:
        """Repair-path write: re-lay one validator-history row (a FULL row
        when ``vals`` is given, else a back-pointer to ``last_changed``)."""
        if vals is not None:
            self._save_validators(height, height, vals)
        else:
            self._set(_val_key(height),
                      proto.Writer().varint(2, last_changed).out())

    def params_last_changed(self, height: int) -> int | None:
        """Pointer twin of :meth:`validators_last_changed` for the
        consensus-params history (used by the state repairer)."""
        f = self._load_checked(_params_key(height), proto.fields)
        if f is None:
            return None
        return height if 1 in f else proto.as_sint64(f.get(2, [0])[-1])

    # --- consensus params history ------------------------------------------

    def _save_params(self, height: int, last_changed: int, params: ConsensusParams) -> None:
        if last_changed == height:
            body = proto.Writer().message(1, params.marshal(), always=True).varint(2, last_changed).out()
        else:
            body = proto.Writer().varint(2, last_changed).out()
        self._set(_params_key(height), body)

    def load_consensus_params(self, height: int) -> ConsensusParams:
        f = self._load_checked(_params_key(height), proto.fields)
        if f is None:
            raise StateStoreError(f"could not find consensus params for height #{height}")
        if 1 in f:
            return ConsensusParams.unmarshal(f[1][-1])
        last_changed = proto.as_sint64(f.get(2, [0])[-1])
        f2 = self._load_checked(_params_key(last_changed), proto.fields)
        if f2 is None:
            raise StateStoreError(f"could not find consensus params for height #{height}")
        return ConsensusParams.unmarshal(f2[1][-1])

    # --- ABCI responses ----------------------------------------------------

    def save_abci_responses(self, height: int, responses: ABCIResponses) -> None:
        if not _trace.ENABLED:
            self._set(_abci_key(height), responses.marshal())
            return
        tr = _trace.current()
        with tr.span("state.save_responses", height=height,
                     txs=len(responses.deliver_txs)):
            raw = responses.marshal()
            self._set(_abci_key(height), raw)
            tr.annotate(bytes=len(raw))

    def load_abci_responses(self, height: int) -> ABCIResponses:
        resp = self._load_checked(_abci_key(height), ABCIResponses.unmarshal)
        if resp is None:
            raise StateStoreError(f"could not find ABCI responses for height #{height}")
        return resp

    # --- pruning -----------------------------------------------------------

    def prune_states(self, base: int, height: int) -> None:
        """Deletes history in [base, height) (reference: state/store.go:243-330).

        Surviving heights may hold back-pointers into the pruned range, so the
        retain boundary `height` is first rewritten as FULL validator/params
        rows (the reference does the same with its keepVals/keepParams sets)."""
        if base <= 0 or height <= base:
            raise StateStoreError(f"invalid range {base}..{height}")
        # Materialize the boundary rows before deleting what they point into.
        boundary_vals = self.load_validators(height)
        self._save_validators(height, height, boundary_vals)
        try:
            boundary_params = self.load_consensus_params(height)
            self._save_params(height, height, boundary_params)
        except StateStoreError:
            pass
        # A pointer one past the boundary (height+1 row saved by save()) may
        # also reference the pruned range.
        try:
            next_vals = self.load_validators(height + 1)
            self._save_validators(height + 1, height + 1, next_vals)
        except ErrNoValSetForHeight:
            pass
        deletes = []
        for h in range(base, height):
            if h % VALSET_CHECK_INTERVAL != 0:
                deletes.append(_val_key(h))
            deletes.append(_params_key(h))
            deletes.append(_abci_key(h))
        self._db.write_batch([], deletes)
