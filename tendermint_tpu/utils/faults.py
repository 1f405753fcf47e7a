"""Deterministic fault injection: named sites, seeded triggers, replayable
schedules (supersedes utils/fail.py; reference: libs/fail/fail.go:10-38 and
the WAL crash-recovery discipline of consensus/replay_test.go).

A fault *site* is a named choke point the framework passes through on its
hot paths -- WAL appends and fsyncs, store writes, p2p send/recv/dial, ABCI
socket round trips, batch-verifier device dispatch, and the five
finalize-commit crash sites. Instrumented modules call ``fire(site)`` (or a
site-shaped helper like ``torn_write``/``maybe_drop``); with no rules
configured that is one attribute read, so production pays nothing.

A *rule* attaches an action to a site. Rules come from the environment
(``TMTPU_FAULTS``) or the in-process API (``configure``):

    TMTPU_FAULT_SEED=1234
    TMTPU_FAULTS="wal.write:torn@12,ops.ed25519.device:raise%0.5x2"

Rule grammar: ``site:action[~param][@nth|%prob][xtimes]``
  * ``@nth``  -- fire on exactly the Nth hit of the site (1-based). Fires
    once unless ``xtimes`` widens it (then on hits N, N+1, ... N+times-1).
  * ``%prob`` -- fire each hit with probability ``prob``; the decision for
    hit k of a site is a pure function of (seed, site, k), so a schedule is
    replayable from the seed alone regardless of thread interleavings
    across sites.
  * no trigger -- fire on every hit.
  * ``~param`` -- action parameter (delay seconds; torn/partial cut byte).

Actions:
  * ``crash``      -- hard process exit (``os._exit(1)``; ``crash_fn``
    replaceable so in-process tests can observe the "crash" as an
    exception).
  * ``raise``      -- raise :class:`FaultInjected` into the caller.
  * ``delay``      -- sleep ``param`` seconds (default 0.05).
  * ``torn``       -- (write sites) append a prefix of the frame cut inside
    the BODY, fsync, then crash: a torn frame on disk.
  * ``partial``    -- like ``torn`` but cut inside the length/crc header.
  * ``drop``       -- (message sites) silently discard the message; at a
    record-read site the row reads back as missing.
  * ``disconnect`` -- (p2p sites) raise :class:`FaultDisconnect`, which the
    connection error path turns into a peer teardown.
  * ``bitrot``     -- (record-read sites, ``store.*.load``) flip one
    deterministic bit in the value on its way out of the DB.
  * ``truncate``   -- (record-read sites) cut the value to a deterministic
    prefix — a torn at-rest record.

The legacy ``TMTPU_FAIL_INDEX`` global-counter contract of utils/fail.py is
preserved verbatim by :func:`fail_point` (the crash matrix in
tests/test_fastsync_recovery.py depends on its exact counting).
"""

from __future__ import annotations

import os
import random
import re
import threading
import time
from dataclasses import dataclass, field


class FaultError(Exception):
    pass


class FaultInjected(FaultError):
    """Raised into the instrumented component by a ``raise`` rule."""

    def __init__(self, site: str):
        self.site = site
        super().__init__(f"fault injected at site {site!r}")


class FaultDisconnect(FaultInjected):
    """A ``disconnect`` rule: the connection layer treats it as a fatal
    transport error (peer teardown + persistent-peer reconnect)."""


# The operator-facing site contract. fire()/check() auto-register unknown
# names, but everything the framework instruments is declared here so
# docs/FAULTS.md, the crash matrix, and sites() can never drift apart.
CANONICAL_SITES: dict[str, str] = {
    "wal.write": "WAL frame append (consensus/wal.py _write_locked), one "
                 "hit a frame, a drain's frames too; "
                 "torn/partial leave a cut frame on disk then crash",
    "wal.fsync": "before the fsync of WAL write_sync/flush_and_sync; "
                 "crash here loses buffered frames",
    "store.block.save": "before BlockStore.save_block's atomic batch write",
    "store.state.save": "before StateStore.save writes the state key "
                        "(after the validator/params history rows)",
    "store.block.load": "every BlockStore record read (meta/part/commit/"
                        "seen-commit/BH/state rows), pre-decode; bitrot/"
                        "truncate mutate the value in flight, drop loses it",
    "store.state.load": "every StateStore record read (state key, validator/"
                        "params history, ABCI responses), pre-decode",
    "store.evidence.load": "every evidence-pool record read (pending/"
                           "committed rows), pre-decode",
    "store.txindex.load": "every tx/block-indexer record read (documents "
                          "and event postings), pre-decode",
    "p2p.send": "outbound MConnection message (drop/delay/disconnect)",
    "p2p.recv": "inbound MConnection message, pre-delivery "
                "(drop/delay/disconnect)",
    "p2p.dial": "Transport.dial of an outbound peer (raise/delay)",
    "abci.call": "one ABCI socket round trip (raise/delay/crash)",
    "mempool.ingest": "one batched CheckTx dispatch of the ingestion front "
                      "door (mempool check_tx_batch + the batched recheck); "
                      "failures degrade to the serial per-tx CheckTx loop",
    "abci.deliver_batch": "one batched DeliverTx chunk dispatch of the "
                          "execution plane (state/execution.py "
                          "deliver_block_txs); fires BEFORE the dispatch, "
                          "so an injected failure degrades that chunk to "
                          "the serial per-tx DeliverTx loop without "
                          "double-applying any tx",
    "ops.ed25519.device": "ed25519 batch-verifier device dispatch; failures "
                          "trip the circuit breaker onto the host fallback",
    "ops.sr25519.device": "sr25519 batch-verifier device dispatch (twin "
                          "breaker)",
    "ops.ed25519.probe": "the breaker's background device re-probe; a "
                         "SEPARATE site so probe timing never consumes "
                         "consensus-path hit indices (replayability)",
    "ops.sr25519.probe": "sr25519 twin of ops.ed25519.probe",
    "consensus.finalize.save_block": "finalize-commit crash site 1 "
                                     "(reference state.go:1605)",
    "consensus.finalize.end_height": "crash site 2: before the WAL "
                                     "EndHeight fsync (state.go:1619)",
    "consensus.finalize.apply_block": "crash site 3: before apply_block "
                                      "(state.go:1642)",
    "consensus.finalize.prune": "crash site 4: before pruning "
                                "(state.go:1667)",
    "consensus.finalize.done": "crash site 5: after update_to_state "
                               "(state.go:1685)",
    "light.gateway.fetch": "one provider fetch attempt inside the light "
                           "gateway (light/gateway.py); raise/delay exercise "
                           "retry with backoff, hedged secondaries, and "
                           "provider-scoreboard demotion/failover",
}

_SPEC_RE = re.compile(
    r"^(?P<action>[a-z_]+)"
    r"(?:~(?P<param>[0-9.]+))?"
    r"(?:@(?P<nth>\d+)|%(?P<prob>[0-9.]+))?"
    r"(?:x(?P<times>\d+))?$"
)

_ACTIONS = {"crash", "raise", "delay", "torn", "partial", "drop", "disconnect",
            "bitrot", "truncate"}


@dataclass
class Rule:
    site: str
    action: str
    param: float | None = None
    nth: int | None = None       # 1-based hit index
    prob: float | None = None
    times: int | None = None     # max firings (None = unlimited for %/always)
    fired: int = field(default=0, compare=False)

    @staticmethod
    def parse(spec: str) -> "Rule":
        """``site:action[~param][@nth|%prob][xtimes]`` -> Rule."""
        site, sep, rest = spec.strip().partition(":")
        m = _SPEC_RE.match(rest) if sep else None
        if not site or m is None or m.group("action") not in _ACTIONS:
            raise ValueError(f"bad fault spec {spec!r} "
                             "(want site:action[~p][@n|%p][xk])")
        nth = int(m.group("nth")) if m.group("nth") else None
        times = int(m.group("times")) if m.group("times") else None
        if nth is not None and times is None:
            times = 1
        return Rule(
            site=site, action=m.group("action"),
            param=float(m.group("param")) if m.group("param") else None,
            nth=nth,
            prob=float(m.group("prob")) if m.group("prob") else None,
            times=times,
        )


@dataclass
class Hit:
    """One triggered rule at one site hit."""

    site: str
    action: str
    rule: Rule
    hit_index: int  # 1-based per-site hit counter value
    rng: random.Random  # deterministic per-(seed, site, hit) decision rng


class Registry:
    """Fault-site registry: site table, rules, per-site hit counters.

    ``check`` is the one decision point: it counts the hit and returns the
    first matching non-exhausted rule (or None). All trigger decisions are
    pure functions of (seed, site, per-site hit index), so any schedule is
    replayable from the seed even when sites interleave across threads.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sites: dict[str, str] = dict(CANONICAL_SITES)
        self._rules: dict[str, list[Rule]] = {}
        self._hits: dict[str, int] = {}
        self.seed = 0
        self.active = False
        self._programmatic = False  # rules came from configure(), not env
        # Replaceable so in-process tests can observe a "crash" as an
        # exception instead of losing the pytest process.
        self.crash_fn = lambda: os._exit(1)

    # --- configuration -----------------------------------------------------

    def register(self, site: str, description: str = "") -> str:
        with self._lock:
            self._sites.setdefault(site, description)
        return site

    def sites(self) -> dict[str, str]:
        with self._lock:
            return dict(self._sites)

    def configure(self, specs, seed: int | None = None,
                  _from_env: bool = False) -> None:
        """Replace all rules with ``specs`` (Rule objects or spec strings)
        and reset hit counters, so a run is replayable from scratch."""
        rules: dict[str, list[Rule]] = {}
        for s in specs:
            r = s if isinstance(s, Rule) else Rule.parse(s)
            rules.setdefault(r.site, []).append(r)
        with self._lock:
            self._rules = rules
            self._hits = {}
            for rs in rules.values():
                for r in rs:
                    r.fired = 0
                    self._sites.setdefault(r.site, "")
            if seed is not None:
                self.seed = seed
            self.active = bool(rules)
            self._programmatic = bool(rules) and not _from_env

    def install_from_env(self) -> None:
        """(Re)load TMTPU_FAULTS / TMTPU_FAULT_SEED. Called at import and
        again from node startup so subprocess runs always start from hit
        counter zero. An explicit env spec wins; with NOTHING in the env,
        rules installed in-process via configure() are left untouched (an
        in-process chaos harness that starts a Node must not have its
        schedule silently wiped)."""
        spec = os.environ.get("TMTPU_FAULTS", "")
        seed = int(os.environ.get("TMTPU_FAULT_SEED", "0") or 0)
        specs = [t for t in spec.split(",") if t.strip()]
        if not specs and self._programmatic:
            return
        self.configure(specs, seed=seed, _from_env=True)

    def clear(self) -> None:
        self.configure([])

    def reset(self, seed: int | None = None) -> None:
        """Zero hit counters and rule fired-counts (same rules): replay."""
        with self._lock:
            self._hits = {}
            for rs in self._rules.values():
                for r in rs:
                    r.fired = 0
            if seed is not None:
                self.seed = seed

    # --- the decision point ------------------------------------------------

    def snapshot(self) -> tuple[dict[str, int], dict[tuple[str, str], int]]:
        """Observability: (per-site hit counts, fired counts keyed
        (site, action)). Consumed by the node metrics sampler so chaos runs
        are visible on the /metrics route."""
        with self._lock:
            hits = dict(self._hits)
            fired: dict[tuple[str, str], int] = {}
            for site, rs in self._rules.items():
                for r in rs:
                    if r.fired:
                        key = (site, r.action)
                        fired[key] = fired.get(key, 0) + r.fired
        return hits, fired

    def check(self, site: str) -> Hit | None:
        if not self.active:
            return None
        with self._lock:
            rules = self._rules.get(site)
            if not rules:
                return None
            idx = self._hits.get(site, 0) + 1
            self._hits[site] = idx
            for r in rules:
                if r.times is not None and r.fired >= r.times:
                    continue
                rng = random.Random(f"{self.seed}:{site}:{idx}")
                if r.nth is not None:
                    if idx < r.nth:
                        continue
                elif r.prob is not None and rng.random() >= r.prob:
                    continue
                r.fired += 1
                return Hit(site=site, action=r.action, rule=r,
                           hit_index=idx, rng=rng)
        return None


REGISTRY = Registry()


# ---------------------------------------------------------------------------
# Site-shaped helpers (what instrumented modules actually call)
# ---------------------------------------------------------------------------


def register(site: str, description: str = "") -> str:
    return REGISTRY.register(site, description)


def sites() -> dict[str, str]:
    return REGISTRY.sites()


def configure(specs, seed: int | None = None) -> None:
    REGISTRY.configure(specs, seed=seed)


def install_from_env() -> None:
    REGISTRY.install_from_env()


def clear() -> None:
    REGISTRY.clear()


def reset(seed: int | None = None) -> None:
    REGISTRY.reset(seed=seed)


def check(site: str) -> Hit | None:
    return REGISTRY.check(site)


def snapshot() -> tuple[dict[str, int], dict[tuple[str, str], int]]:
    return REGISTRY.snapshot()


def _apply(hit: Hit) -> None:
    if hit.action == "crash":
        REGISTRY.crash_fn()
        raise FaultInjected(hit.site)  # crash_fn was stubbed to return
    if hit.action == "raise":
        raise FaultInjected(hit.site)
    if hit.action == "disconnect":
        raise FaultDisconnect(hit.site)
    if hit.action == "delay":
        time.sleep(hit.rule.param if hit.rule.param is not None else 0.05)
        return
    # torn/partial at a non-write site, drop at a non-message site: the
    # schedule is misconfigured. A chaos rule that silently injects nothing
    # would validate nothing -- fail loudly instead.
    raise FaultError(
        f"action {hit.action!r} is not supported at site {hit.site!r}")


def fire(site: str, local: str = "", remote: str = "") -> None:
    """Apply any triggered crash/raise/disconnect/delay rule at ``site``.
    Write-shaped (torn/partial) and message-shaped (drop) actions need the
    site-specific helpers below; a firing that lands here raises
    FaultError so a misconfigured schedule can never pass silently.

    ``local``/``remote`` carry peer-id context at the p2p sites; when
    given, the peer-scoped nemesis plane (utils/nemesis.py) is consulted
    after the global site rules (a dial across a partition raises
    FaultInjected here)."""
    hit = REGISTRY.check(site)
    if hit is not None:
        _apply(hit)
    if local or remote:
        from tendermint_tpu.utils import nemesis

        if nemesis.PLANE.active:
            nemesis.PLANE.outcome(site, local, remote)


def maybe_drop(site: str) -> bool:
    """Message sites (p2p.send/p2p.recv): True when the message should be
    silently discarded; delay sleeps first; disconnect/crash/raise apply."""
    hit = REGISTRY.check(site)
    if hit is None:
        return False
    if hit.action == "drop":
        return True
    _apply(hit)
    return False


def link_outcome(site: str, local: str = "", remote: str = "",
                 channel: int | None = None) -> str:
    """Message sites with peer-id context (MConnection send/recv): the
    global site rules fire first (exact :func:`maybe_drop` semantics),
    then the peer-scoped nemesis plane. Returns ``'pass'``, ``'drop'``,
    or ``'dup'`` (deliver twice); delay rules sleep here; disconnect
    raises FaultDisconnect for the connection error path."""
    if maybe_drop(site):
        return "drop"
    from tendermint_tpu.utils import nemesis

    if not nemesis.PLANE.active:
        return "pass"
    return nemesis.PLANE.outcome(site, local, remote, channel)


def mutate_value(site: str, value: bytes | None) -> bytes | None:
    """Record-read sites (store.*.load): apply a bit-rot / truncation rule
    to the value on its way out of the DB — what the integrity envelope
    (store/envelope.py) exists to catch. Returns the value unchanged when
    no rule fires; missing rows (None) never consume a hit (a row that is
    not there cannot rot).

    * ``bitrot``   -- flip ONE deterministic bit (``~p`` pins the byte
      index; otherwise seeded from (seed, site, hit)).
    * ``truncate`` -- cut the value to a deterministic prefix, possibly
      empty (``~p`` pins the cut length).
    * ``drop``     -- the record reads back as missing.
    * crash/raise/delay apply as at any other site.
    """
    if value is None:
        return None
    hit = REGISTRY.check(site)
    if hit is None:
        return value
    if hit.action == "bitrot":
        if not value:
            return value
        if hit.rule.param is not None:
            pos = min(int(hit.rule.param), len(value) - 1)
            bit = 0
        else:
            pos = hit.rng.randrange(len(value))
            bit = hit.rng.randrange(8)
        return value[:pos] + bytes([value[pos] ^ (1 << bit)]) + value[pos + 1:]
    if hit.action == "truncate":
        if hit.rule.param is not None:
            cut = min(int(hit.rule.param), len(value))
        else:
            cut = hit.rng.randrange(len(value)) if value else 0
        return value[:cut]
    if hit.action == "drop":
        return None
    _apply(hit)
    return value


def corrupt_db(db, key: bytes, mode: str = "bitrot", seed: int = 0) -> bytes:
    """Offline at-rest mutation: deterministically bit-rot or truncate the
    stored value at ``key`` IN the DB (the scrub matrix and the soak
    ``bitrot`` perturbation drive this — a live rule mutates reads in
    flight, this damages the bytes on disk). Returns the original value so
    a harness can assert the repaired row is byte-identical."""
    raw = db.get(key)
    if raw is None:
        raise FaultError(f"corrupt_db: no record at key {key!r}")
    rng = random.Random(f"{seed}:corrupt_db:{mode}:{key.hex()}")
    if mode == "bitrot":
        pos = rng.randrange(len(raw))
        bit = rng.randrange(8)
        db.set(key, raw[:pos] + bytes([raw[pos] ^ (1 << bit)]) + raw[pos + 1:])
    elif mode == "truncate":
        db.set(key, raw[:rng.randrange(len(raw))])
    else:
        raise FaultError(f"corrupt_db: unknown mode {mode!r} "
                         "(want bitrot|truncate)")
    return raw


def tear_wal_tail(wal_dir: str, mode: str = "torn", seed: int = 0) -> int:
    """Offline WAL-tail damage: cut the LAST frame of the newest chunk in
    ``wal_dir`` — `corrupt_db`'s sibling for the consensus WAL. The live
    ``wal.write`` torn/partial rules reproduce a power cut mid-append on a
    running node; this one damages an ABANDONED home (the fabric's
    hard-kill path tears the tail after the incarnation is gone, so a
    reboot must drive `WAL._repair` exactly as a real torn crash would).

    ``torn`` re-cuts the final frame inside its body (header intact, body
    short); ``partial`` cuts inside the 8 header bytes. Returns the number
    of bytes removed (0 when the log has no frame to tear)."""
    if mode not in ("torn", "partial"):
        raise FaultError(f"tear_wal_tail: unknown mode {mode!r} "
                         "(want torn|partial)")
    chunks = sorted(name for name in os.listdir(wal_dir)
                    if name.startswith("wal.") and name[4:].isdigit())
    if not chunks:
        return 0
    path = os.path.join(wal_dir, chunks[-1])
    with open(path, "rb") as f:
        data = f.read()
    # find the last frame boundary with the WAL's own validity scan
    from tendermint_tpu.consensus import wal as cwal

    last_start = None
    end = 0
    for pos, fend, _t, _m in cwal._valid_frames(data):
        last_start, end = pos, fend
    if last_start is None or end < len(data):
        return 0  # empty log, or the tail is already damaged
    frame = data[last_start:end]
    if len(frame) < 2:
        return 0
    rng = random.Random(f"{seed}:tear_wal_tail:{mode}:{chunks[-1]}")
    if mode == "partial":
        cut = rng.randint(1, min(7, len(frame) - 1))
    else:
        cut = rng.randint(min(8, len(frame) - 1), len(frame) - 1)
    with open(path, "wb") as f:
        f.write(data[:last_start] + frame[:cut])
        f.flush()
        os.fsync(f.fileno())
    return len(frame) - cut


def torn_write(site: str, fobj, frame: bytes) -> None:
    """Write sites (WAL append): on a torn/partial rule, write a
    deterministic prefix of ``frame``, push it to disk, and crash -- the
    on-disk tail is exactly what a power cut mid-append leaves. Returns
    normally when no rule fires (the caller then writes the full frame).

    ``torn`` cuts inside the frame body (a valid-looking header with a
    short body); ``partial`` cuts inside the first 8 header bytes. ``~p``
    overrides the cut byte."""
    hit = REGISTRY.check(site)
    if hit is None:
        return
    if hit.action in ("torn", "partial"):
        if hit.rule.param is not None:
            cut = max(1, min(int(hit.rule.param), len(frame) - 1))
        elif hit.action == "partial":
            cut = hit.rng.randint(1, min(7, len(frame) - 1))
        else:
            cut = hit.rng.randint(min(8, len(frame) - 1), len(frame) - 1)
        fobj.write(frame[:cut])
        try:
            fobj.flush()
            os.fsync(fobj.fileno())
        except (OSError, ValueError):
            pass
        REGISTRY.crash_fn()
        raise FaultInjected(site)  # crash_fn was stubbed to return
    _apply(hit)


def crash_point(site: str) -> None:
    """Crash-class site: apply crash/raise/delay rules (alias of fire with
    a name that reads right at commit-path call sites)."""
    fire(site)


# ---------------------------------------------------------------------------
# Legacy utils/fail.py contract (reference: libs/fail/fail.go:10-38)
# ---------------------------------------------------------------------------

_legacy_counter = 0


def fail_point(site: str | None = None) -> None:
    """Set TMTPU_FAIL_INDEX=N to make the N-th fail_point() call in the
    process exit hard, simulating a crash between commit steps (exact
    utils/fail.py semantics, counter shared across all call sites). When a
    ``site`` name is given the named-site rules fire too."""
    global _legacy_counter
    target = os.environ.get("TMTPU_FAIL_INDEX")
    if target is not None:
        if _legacy_counter == int(target):
            REGISTRY.crash_fn()
        _legacy_counter += 1
    if site is not None:
        fire(site)


# Environment config is live from import: child processes (crash matrix
# subprocesses, e2e nodes) inherit TMTPU_FAULTS and need no wiring call.
REGISTRY.install_from_env()
