"""Light client (reference: light/client.go).

Verifies headers from a primary provider against a trust anchor, using
sequential or skipping (bisection) verification, cross-checks every newly
verified header against witness providers (detector.py), and persists
verified blocks in a trusted store.

TPU angle: every commit check inside verify funnels through the batched
BatchVerifier (types/validator_set.py), so one bisection step costs at most
two kernel flushes. Sequential mode walks its range in bounded windows: each
is fetched, verified by range_verify.verify_window (the signatures of the
whole window in a few chunk-sized launches, the per-header loop's verdict
and side effects) and saved before the next is fetched.
"""

from __future__ import annotations

from dataclasses import dataclass

from tendermint_tpu.light import range_verify
from tendermint_tpu.light import verifier as lv
from tendermint_tpu.light.detector import (
    compare_first_header_with_witnesses,
    detect_divergence,
)
from tendermint_tpu.light.provider import (
    ErrLightBlockNotFound,
    Provider,
    ProviderError,
)
from tendermint_tpu.light.store import DBStore
from tendermint_tpu.light.verifier import (
    DEFAULT_TRUST_LEVEL,
    ErrOldHeaderExpired,
    LightClientError,
    validate_trust_level,
)
from tendermint_tpu.types.light_block import LightBlock
from tendermint_tpu.types.ttime import Time

SEQUENTIAL = "sequential"
SKIPPING = "skipping"

DEFAULT_PRUNING_SIZE = 1000
DEFAULT_MAX_CLOCK_DRIFT_S = 10.0
DEFAULT_MAX_RETRY_ATTEMPTS = 10
# A pivot lies 9/16 of the way from the verified block to the one refused:
# the cached blocks of the batch before always lie past 1/2, so 9/16 finds
# something in between (reference: light/client.go verifySkippingNumerator).
VERIFY_SKIPPING_NUMERATOR = 9
VERIFY_SKIPPING_DENOMINATOR = 16


@dataclass
class TrustOptions:
    """Trust anchor (reference: light/client.go:58-84 TrustOptions)."""

    period_s: float
    height: int
    hash: bytes

    def validate_basic(self) -> None:
        if self.period_s <= 0:
            raise LightClientError("negative or zero trusting period")
        if self.height <= 0:
            raise LightClientError("negative or zero height")
        if len(self.hash) != 32:
            raise LightClientError(
                f"expected hash size to be 32 bytes, got {len(self.hash)} bytes"
            )


from tendermint_tpu.light.detector import ErrNoWitnesses  # noqa: E402  (re-export)


def _count(metric: str, n: int = 1, **labels) -> None:
    """Add to a NodeMetrics counter where a node exposes metrics."""
    from tendermint_tpu.utils import metrics as tmmetrics

    m = tmmetrics.GLOBAL_NODE_METRICS
    if m is not None:
        getattr(m, metric).add(n, **labels)


def _gauge_max(metric: str, value: int) -> None:
    """Raise a NodeMetrics gauge to `value` where a node exposes metrics."""
    from tendermint_tpu.utils import metrics as tmmetrics

    m = tmmetrics.GLOBAL_NODE_METRICS
    if m is not None:
        getattr(m, metric).raise_to(value)


class Client:
    """reference: light/client.go:174 (Client struct), :225 NewClient."""

    def __init__(
        self,
        chain_id: str,
        trust_options: TrustOptions,
        primary: Provider,
        witnesses: list[Provider],
        trusted_store: DBStore,
        *,
        verification_mode: str = SKIPPING,
        trust_level: tuple[int, int] = DEFAULT_TRUST_LEVEL,
        max_clock_drift_s: float = DEFAULT_MAX_CLOCK_DRIFT_S,
        pruning_size: int = DEFAULT_PRUNING_SIZE,
        logger=None,
    ):
        import threading

        # one lock around all public verification entry points (the Go
        # reference holds c.mtx); providers/stores are not thread-safe
        self._mtx = threading.RLock()
        if verification_mode not in (SEQUENTIAL, SKIPPING):
            raise LightClientError(f"unknown verification mode {verification_mode}")
        validate_trust_level(trust_level)
        trust_options.validate_basic()
        self.chain_id = chain_id
        self.trusting_period_s = trust_options.period_s
        self.verification_mode = verification_mode
        self.trust_level = trust_level
        self.max_clock_drift_s = max_clock_drift_s
        self.primary = primary
        self.witnesses = list(witnesses)
        self.had_witnesses = bool(witnesses)
        self.trusted_store = trusted_store
        self.pruning_size = pruning_size
        self.logger = logger
        # substantiated attacks the detector proved (light/detector.py
        # Divergence records): the live-attack harness reads the built
        # evidence from here after ErrConflictingHeaders surfaces
        self.divergences: list = []
        # dedup keys for Divergence records: (witness identity, header hash)
        self._divergence_keys: set = set()
        # windows of a sequential sync that had to be re-run header by
        # header for another reason than a refused header (0 when healthy)
        self.range_fallbacks = 0
        # the attempts of the last bisection, in order: (from, to, accepted)
        self.last_bisection: list[tuple[int, int, bool]] = []
        self.latest_trusted: LightBlock | None = trusted_store.latest_light_block()
        if self.latest_trusted is None:
            self._initialize(trust_options)
        else:
            self._check_trusted_header_using_options(trust_options)

    # --- initialization (reference: light/client.go:352-431) ---------------

    def _initialize(self, opts: TrustOptions) -> None:
        lb = self._light_block_from_primary(opts.height)
        # Ensure the header matches the trusted hash, then self-verify:
        # 2/3 of the block's OWN validator set must have signed
        # (reference: light/client.go:381-418).
        if lb.hash() != opts.hash:
            raise LightClientError(
                f"expected header's hash {opts.hash.hex()}, but got {lb.hash().hex()}"
            )
        lb.validate_basic(self.chain_id)
        lb.validator_set.verify_commit_light(
            self.chain_id,
            lb.signed_header.commit.block_id,
            lb.height,
            lb.signed_header.commit,
        )
        compare_first_header_with_witnesses(self, lb.signed_header)
        self._update_trusted_light_block(lb)

    def _check_trusted_header_using_options(self, opts: TrustOptions) -> None:
        """Existing trusted state vs new options (reference:
        light/client.go:272-350 checkTrustedHeaderUsingOptions)."""
        primary_hash = None
        if self.latest_trusted.height >= opts.height:
            stored = self.trusted_store.light_block(opts.height)
            if stored is not None:
                primary_hash = stored.hash()
        if primary_hash is None:
            lb = self._light_block_from_primary(opts.height)
            primary_hash = lb.hash()
        if primary_hash != opts.hash:
            # Trust anchor changed: wipe and restart from options.
            self._cleanup()
            self._initialize(opts)

    # --- public API --------------------------------------------------------

    def trusted_light_block(self, height: int) -> LightBlock:
        """reference: light/client.go:1011 TrustedLightBlock."""
        latest = self.latest_trusted
        if latest is None:
            raise LightClientError("no trusted state yet")
        if height > latest.height:
            raise LightClientError(
                f"height requested is too high: {height} vs latest {latest.height}"
            )
        lb = self.trusted_store.light_block(height)
        if lb is None:
            raise LightClientError(f"no light block at height {height}")
        return lb

    def first_trusted_height(self) -> int:
        return self.trusted_store.first_light_block_height()

    def update(self, now: Time) -> LightBlock | None:
        """Verify the latest header from primary if newer than latest trusted
        (reference: light/client.go:443 Update)."""
        with self._mtx:
            latest_trusted = self.latest_trusted
            if latest_trusted is None:
                raise LightClientError("no trusted state yet")
            latest = self._light_block_from_primary(0)
            if latest.height > latest_trusted.height:
                self.verify_light_block(latest, now)
                return latest
            return None

    def verify_light_block_at_height(self, height: int, now: Time) -> LightBlock:
        """reference: light/client.go:474 VerifyLightBlockAtHeight."""
        with self._mtx:
            if height <= 0:
                raise LightClientError("negative or zero height")
            lb = self.trusted_store.light_block(height)
            if lb is not None:
                return lb
            latest = self.latest_trusted
            tr = range_verify.tracer()
            with range_verify.span(
                    tr, "light.sync", mode=self.verification_mode, to=height,
                    **{"from": latest.height if latest is not None else 0}):
                with range_verify.span(tr, "light.fetch", headers=1,
                                       **{"from": height}):
                    lb = self._light_block_from_primary(height)
                self.verify_light_block(lb, now)
            return lb

    def verify_light_block(self, new_lb: LightBlock, now: Time) -> None:
        """reference: light/client.go:525 VerifyHeader (+ :558
        verifyLightBlock)."""
        with self._mtx:
            self._verify_light_block_locked(new_lb, now)

    def _verify_light_block_locked(self, new_lb: LightBlock, now: Time) -> None:
        h = self.trusted_store.light_block(new_lb.height)
        if h is not None:
            if h.hash() == new_lb.hash():
                return
            raise LightClientError(
                f"existing trusted header {h.hash().hex()} does not match "
                f"new header {new_lb.hash().hex()}"
            )
        new_lb.validate_basic(self.chain_id)

        latest = self.latest_trusted
        if latest is not None and new_lb.height < latest.height:
            # Historical header: find closest trusted below, verify forward,
            # or walk backwards from the first trusted block
            # (reference: light/client.go:558-600 verifyLightBlock).
            closest = self.trusted_store.light_block_before(new_lb.height)
            if closest is not None:
                self._verify_from(closest, new_lb, now)
            else:
                first = self.trusted_store.light_block(self.first_trusted_height())
                self._backwards(first, new_lb)
        else:
            anchor = latest
            if anchor is None:
                raise LightClientError("no trusted state yet")
            self._verify_from(anchor, new_lb, now)

        detect_divergence(self, new_lb, now)
        self._update_trusted_light_block(new_lb)

    # --- verification strategies ------------------------------------------

    def _verify_from(self, trusted: LightBlock, new_lb: LightBlock, now: Time) -> None:
        if self.verification_mode == SEQUENTIAL:
            self._verify_sequential(trusted, new_lb, now)
        else:
            self._verify_skipping_against_primary(trusted, new_lb, now)

    def _verify_sequential(self, trusted: LightBlock, new_lb: LightBlock, now: Time) -> None:
        """Verify every header in (trusted, new] (reference:
        light/client.go:613 verifySequential), a window at a time: fetch a
        window from the primary, verify it through the range path
        (range_verify.verify_window), save it, then fetch the next. What
        the caller sees and what the trusted store holds afterwards are
        `_verify_sequential_per_header`'s."""
        tr = range_verify.tracer()
        store = self.trusted_store

        def save(lb: LightBlock) -> int | None:
            return None if lb is new_lb else store.save_light_block(lb)

        verified = trusted
        while verified.height < new_lb.height:
            with range_verify.span(tr, "light.fetch", **{"from": verified.height + 1}):
                window, fetch_error, promotions = self._fetch_window(
                    verified.height + 1, new_lb)
                if tr is not None:
                    tr.annotate(headers=len(window))
            try:
                n, refusal = range_verify.verify_window(
                    verified, window, self.trusting_period_s, now,
                    self.max_clock_drift_s, save, tr)
            except Exception:  # noqa: BLE001 - the machinery, not a verdict
                self._undo_promotions(promotions, verified.height)
                self.range_fallbacks += 1
                _count("light_range_fallbacks")
                with range_verify.span(tr, "light.range", fallback=1,
                                       headers=new_lb.height - verified.height):
                    return self._verify_sequential_per_header(verified, new_lb, now)
            _count("light_headers_verified", n, mode=SEQUENTIAL)
            if refusal is not None:
                self._undo_promotions(promotions, window[n].height)
                raise refusal
            if fetch_error is not None:
                raise fetch_error
            verified = window[-1]

    def _fetch_window(self, first: int, new_lb: LightBlock):
        """The light blocks of heights first.. up to range_verify's window
        size or new_lb -> (blocks, error, promotions). A fetch that fails
        ends the window: ``error`` is raised by the caller once the blocks
        below it verified, as the per-header loop would have reached it.
        ``promotions``: (height, primary, witnesses) as they were before each
        fetch that replaced the primary, for `_undo_promotions`."""
        blocks: list[LightBlock] = []
        promotions = []
        room = range_verify.window_slots()
        for height in range(first, new_lb.height + 1):
            if height == new_lb.height:
                lb = new_lb
            else:
                before = (height, self.primary, self.witnesses)
                try:
                    lb = self._light_block_from_primary(height)
                except Exception as e:  # noqa: BLE001 - re-raised in its turn
                    return blocks, e, promotions
                if self.primary is not before[1]:
                    promotions.append(before)
            blocks.append(lb)
            room -= max(1, len(lb.signed_header.commit.signatures))
            if room <= 0:
                break
        return blocks, None, promotions

    def _undo_promotions(self, promotions, height: int) -> None:
        """A window is fetched before it is verified, so a primary that
        failed above a refused header was replaced by a witness the
        per-header loop never asked: put back who served at `height`."""
        for at, primary, witnesses in promotions:
            if at > height:
                self.primary, self.witnesses = primary, witnesses
                return

    def _verify_sequential_per_header(self, trusted: LightBlock,
                                      new_lb: LightBlock, now: Time) -> None:
        """The reference's loop as it is written (light/client.go:613): one
        verify_adjacent, and so one verify_commit_light, per header. The
        rule `_verify_sequential` is held to by the differential tests, and
        what re-runs a window whose machinery failed."""
        verified = trusted
        for height in range(trusted.height + 1, new_lb.height + 1):
            inter = new_lb if height == new_lb.height else self._light_block_from_primary(height)
            lv.verify_adjacent(
                verified.signed_header,
                inter.signed_header,
                inter.validator_set,
                self.trusting_period_s,
                now,
                self.max_clock_drift_s,
            )
            if height != new_lb.height:
                self.trusted_store.save_light_block(inter)
            verified = inter

    def _verify_skipping_against_primary(
        self, trusted: LightBlock, new_lb: LightBlock, now: Time
    ) -> None:
        self._verify_skipping(self.primary, trusted, new_lb, now)

    def _verify_skipping(
        self, source: Provider, trusted: LightBlock, new_lb: LightBlock,
        now: Time, save: bool = True
    ) -> list[LightBlock]:
        """Bisection (reference: light/client.go:706 verifySkipping).

        A cache of the blocks fetched so far, the target first and each
        pivot after the block it halves the way to. An attempt that the
        trusted set cannot vouch for (ErrNewValSetCantBeTrusted) moves one
        place down the cache, and only at its end is a new pivot fetched,
        9/16 of the way from the verified block to the last one refused; an
        attempt that verifies makes its block the verified one, drops it and
        everything below it from the cache, and starts again from the target.
        With save=False nothing is written to the trusted store (the detector
        substantiates a witness's divergent header without polluting trust).

        Every attempt is a ``light.skip.hop`` span and an entry of
        ``last_bisection``; a pivot's fetch is a ``light.skip.fetch`` span.
        """
        tr = range_verify.tracer()
        block_cache = [new_lb]
        verified_blocks = []
        attempts = self.last_bisection = []
        depth = 0
        verified = trusted
        # Captured once: self.primary may be reassigned mid-bisection by a
        # witness promotion inside _light_block_from_primary.
        use_primary = source is self.primary
        while True:
            candidate = block_cache[depth]
            accepted = False
            try:
                with range_verify.span(
                        tr, "light.skip.hop", to=candidate.height, depth=depth,
                        accepted=0, **{"from": verified.height}):
                    try:
                        lv.verify(
                            verified.signed_header,
                            verified.validator_set,
                            candidate.signed_header,
                            candidate.validator_set,
                            self.trusting_period_s,
                            now,
                            self.max_clock_drift_s,
                            self.trust_level,
                        )
                        accepted = True
                    except lv.ErrNewValSetCantBeTrusted:
                        pass
                    if accepted and tr is not None:
                        tr.annotate(accepted=1)
            finally:
                attempts.append((verified.height, candidate.height, accepted))
            if not accepted:
                # Can't skip that far (reference client.go:755-776): the
                # next cached block, or at the cache's end a new pivot.
                _count("light_skip_refused")
                if depth == len(block_cache) - 1:
                    pivot = verified.height + (
                        (candidate.height - verified.height)
                        * VERIFY_SKIPPING_NUMERATOR // VERIFY_SKIPPING_DENOMINATOR)
                    if pivot == verified.height:
                        raise LightClientError(
                            "bisection failed to converge "
                            f"({verified.height} -> {candidate.height})"
                        )
                    with range_verify.span(tr, "light.skip.fetch", height=pivot):
                        if use_primary:  # validate_basic included
                            inter = self._light_block_from_primary(pivot)
                        else:
                            inter = source.light_block(pivot)
                            inter.validate_basic(self.chain_id)
                    block_cache.append(inter)
                depth += 1
                _gauge_max("light_skip_depth_max", depth)
                continue
            # Verified one step.
            _count("light_headers_verified", mode=SKIPPING)
            _count("light_skip_hops")
            if depth == 0:
                return verified_blocks
            verified = candidate
            verified_blocks.append(candidate)
            if save:
                self.trusted_store.save_light_block(candidate)
            block_cache = block_cache[:depth]
            depth = 0

    def _backwards(self, trusted: LightBlock, new_lb: LightBlock) -> None:
        """Hash-linked walk below the first trusted header (reference:
        light/client.go:942 backwards)."""
        verified = trusted.signed_header.header
        for height in range(trusted.height - 1, new_lb.height - 1, -1):
            inter = (
                new_lb
                if height == new_lb.height
                else self._light_block_from_primary(height)
            )
            lv.verify_backwards(inter.signed_header.header, verified)
            verified = inter.signed_header.header

    # --- maintenance -------------------------------------------------------

    def _update_trusted_light_block(self, lb: LightBlock) -> None:
        self.trusted_store.save_light_block(lb)
        if self.pruning_size > 0:
            self.trusted_store.prune(self.pruning_size)
        if self.latest_trusted is None or lb.height > self.latest_trusted.height:
            self.latest_trusted = lb

    def _cleanup(self) -> None:
        """Remove all trusted state (reference: light/client.go:1041)."""
        hs = []
        h = self.trusted_store.first_light_block_height()
        latest = self.trusted_store.latest_light_block()
        if h > 0 and latest is not None:
            hs = range(h, latest.height + 1)
        for height in hs:
            self.trusted_store.delete_light_block(height)
        self.latest_trusted = None

    def _light_block_from_primary(self, height: int) -> LightBlock:
        """Fetch from primary; on failure, promote a witness (reference:
        light/client.go:1080 lightBlockFromPrimary + replacePrimaryProvider)."""
        try:
            lb = self.primary.light_block(height)
            lb.validate_basic(self.chain_id)
            return lb
        except (ProviderError, ValueError) as primary_err:
            if isinstance(primary_err, ErrLightBlockNotFound):
                raise
            # Replace primary with the first responsive witness.
            for i, w in enumerate(self.witnesses):
                try:
                    lb = w.light_block(height)
                    lb.validate_basic(self.chain_id)
                except (ProviderError, ValueError):
                    continue
                self.primary = w
                self.witnesses = self.witnesses[:i] + self.witnesses[i + 1:]
                return lb
            raise

    def remove_witness(self, idx: int) -> None:
        """Drop the witness at idx; tolerant of a concurrent removal having
        already shrunk the list (locked — indices are only meaningful under
        the verification lock)."""
        with self._mtx:
            if 0 <= idx < len(self.witnesses):
                self.witnesses.pop(idx)

    def remove_witnesses(self, providers) -> None:
        """Identity-based removal: each provider leaves the witness list at
        most once, regardless of how indices shifted since the caller
        observed them."""
        with self._mtx:
            seen: set[int] = set()
            for w in providers:
                if id(w) in seen:
                    continue
                seen.add(id(w))
                for i, cur in enumerate(self.witnesses):
                    if cur is w:
                        self.witnesses.pop(i)
                        break

    def add_witness(self, provider: Provider) -> None:
        """Rotate a fresh witness in (gateway witness rotation on
        ErrNoWitnesses)."""
        with self._mtx:
            if provider is not self.primary and \
                    all(w is not provider for w in self.witnesses):
                self.witnesses.append(provider)
                self.had_witnesses = True
