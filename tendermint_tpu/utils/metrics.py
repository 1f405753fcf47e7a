"""Prometheus metrics: registry, Counter/Gauge/Histogram, text exposition,
and the scrape endpoint (reference: the per-subsystem metrics.go files +
node/node.go:1219 startPrometheusServer).

Pure-stdlib implementation of the Prometheus text format v0.0.4 — no
client library is baked into the image, and the format is trivial.
"""

from __future__ import annotations

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


class _Metric:
    def __init__(self, name: str, help_: str, labels: tuple[str, ...]):
        self.name = name
        self.help = help_
        self.label_names = labels
        self._values: dict[tuple, float] = {}
        self._mtx = threading.Lock()

    def _key(self, labels: dict) -> tuple:
        return tuple(str(labels.get(n, "")) for n in self.label_names)

    def _fmt_labels(self, key: tuple) -> str:
        if not self.label_names:
            return ""
        inner = ",".join(f'{n}="{_escape(v)}"' for n, v in zip(self.label_names, key))
        return "{" + inner + "}"


def _escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class Counter(_Metric):
    TYPE = "counter"

    def add(self, delta: float = 1.0, **labels) -> None:
        if delta < 0:
            raise ValueError("counters only go up")
        k = self._key(labels)
        with self._mtx:
            self._values[k] = self._values.get(k, 0.0) + delta

    def expose(self) -> list[str]:
        with self._mtx:
            return [f"{self.name}{self._fmt_labels(k)} {v}"
                    for k, v in sorted(self._values.items())]


class Gauge(_Metric):
    TYPE = "gauge"

    def set(self, value: float, **labels) -> None:
        with self._mtx:
            self._values[self._key(labels)] = float(value)

    def remove(self, **labels) -> None:
        """Drop one labeled series entirely (per-peer gauges must not
        leave a permanent exposition line per identity ever seen)."""
        with self._mtx:
            self._values.pop(self._key(labels), None)

    def add(self, delta: float = 1.0, **labels) -> None:
        k = self._key(labels)
        with self._mtx:
            self._values[k] = self._values.get(k, 0.0) + delta

    def raise_to(self, value: float, **labels) -> None:
        """A high-water mark: set, unless the series already reads higher."""
        k = self._key(labels)
        with self._mtx:
            self._values[k] = max(self._values.get(k, 0.0), float(value))

    def expose(self) -> list[str]:
        with self._mtx:
            return [f"{self.name}{self._fmt_labels(k)} {v}"
                    for k, v in sorted(self._values.items())]


DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0)


class Histogram(_Metric):
    TYPE = "histogram"

    def __init__(self, name, help_, labels, buckets=DEFAULT_BUCKETS):
        super().__init__(name, help_, labels)
        self.buckets = tuple(sorted(buckets))
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}
        self._totals: dict[tuple, int] = {}

    def observe(self, value: float, **labels) -> None:
        k = self._key(labels)
        with self._mtx:
            counts = self._counts.setdefault(k, [0] * len(self.buckets))
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    counts[i] += 1
            self._sums[k] = self._sums.get(k, 0.0) + value
            self._totals[k] = self._totals.get(k, 0) + 1

    def seed(self, **labels) -> None:
        """Pre-seed a labeled series at zero observations, so a healthy
        node scrapes explicit `_bucket`/`_sum`/`_count` zeros instead of an
        absent metric — the histogram twin of the Counter.add(0) discipline
        (tmlint metrics-discipline)."""
        k = self._key(labels)
        with self._mtx:
            self._counts.setdefault(k, [0] * len(self.buckets))
            self._sums.setdefault(k, 0.0)
            self._totals.setdefault(k, 0)

    def expose(self) -> list[str]:
        out = []
        with self._mtx:
            for k, counts in sorted(self._counts.items()):
                base = dict(zip(self.label_names, k))
                for i, ub in enumerate(self.buckets):
                    lk = self._fmt_labels(tuple(list(k)))
                    labels = (lk[:-1] + "," if lk else "{") + f'le="{ub}"' + "}"
                    out.append(f"{self.name}_bucket{labels} {counts[i]}")
                lk = self._fmt_labels(k)
                inf_labels = (lk[:-1] + "," if lk else "{") + 'le="+Inf"}'
                out.append(f"{self.name}_bucket{inf_labels} {self._totals[k]}")
                out.append(f"{self.name}_sum{lk} {self._sums[k]}")
                out.append(f"{self.name}_count{lk} {self._totals[k]}")
        return out


class Registry:
    def __init__(self, namespace: str = "tendermint"):
        self.namespace = namespace
        self._metrics: list[_Metric] = []
        self._mtx = threading.Lock()

    def _register(self, cls, subsystem: str, name: str, help_: str,
                  labels: tuple[str, ...] = (), **kw):
        full = "_".join(p for p in (self.namespace, subsystem, name) if p)
        m = cls(full, help_, labels, **kw)
        with self._mtx:
            self._metrics.append(m)
        return m

    def counter(self, subsystem, name, help_="", labels=()) -> Counter:
        return self._register(Counter, subsystem, name, help_, tuple(labels))

    def gauge(self, subsystem, name, help_="", labels=()) -> Gauge:
        return self._register(Gauge, subsystem, name, help_, tuple(labels))

    def histogram(self, subsystem, name, help_="", labels=(),
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._register(Histogram, subsystem, name, help_, tuple(labels),
                              buckets=buckets)

    def expose(self) -> str:
        lines = []
        with self._mtx:
            metrics = list(self._metrics)
        for m in metrics:
            lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.TYPE}")
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


# --- per-subsystem metric structs (reference: */metrics.go) -----------------


class NodeMetrics:
    """The metric set every node exposes (reference: consensus/metrics.go:11,
    mempool/metrics.go, p2p/metrics.go, state/metrics.go)."""

    def __init__(self, registry: Registry | None = None):
        r = registry if registry is not None else Registry()
        self.registry = r
        # consensus
        self.height = r.gauge("consensus", "height", "Height of the chain.")
        self.rounds = r.gauge("consensus", "rounds", "Number of rounds.")
        self.validators = r.gauge("consensus", "validators", "Number of validators.")
        self.validators_power = r.gauge(
            "consensus", "validators_power", "Total power of all validators.")
        self.missing_validators = r.gauge(
            "consensus", "missing_validators", "Validators missing from the last commit.")
        self.byzantine_validators = r.gauge(
            "consensus", "byzantine_validators", "Validators who tried to double sign.")
        self.block_interval_seconds = r.histogram(
            "consensus", "block_interval_seconds",
            "Time between this and the last block.",
            buckets=(0.1, 0.25, 0.5, 1, 2, 3, 5, 10, 30))
        self.num_txs = r.gauge("consensus", "num_txs", "Number of transactions.")
        self.block_size_bytes = r.gauge(
            "consensus", "total_txs", "Size of the latest block (bytes).")
        self.total_txs = r.counter(
            "consensus", "committed_txs", "Total transactions committed.")
        self.step_duration = r.histogram(
            "consensus", "step_duration_seconds", "Time spent per step.",
            labels=("step",),
            buckets=(0.001, 0.01, 0.05, 0.1, 0.5, 1, 5))
        # flight-recorder phase mirror (utils/trace.py, docs/OBSERVABILITY
        # .md): Tracer._append observes every MIRRORED_SPANS span here, so
        # phase attribution is scrapeable without the TMTPU_TRACE ring
        self.trace_phase_seconds = r.histogram(
            "trace", "phase_seconds",
            "Flight-recorder span durations by phase (utils/trace.py "
            "MIRRORED_SPANS).", labels=("phase",),
            buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1))
        self.batch_verify_seconds = r.histogram(
            "consensus", "batch_verify_seconds",
            "Latency of batched signature verification flushes, by the "
            "route that answered (ops/breaker.ROUTES).", labels=("route",),
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1))
        self.batch_verify_sigs = r.counter(
            "consensus", "batch_verify_sigs_total",
            "Signatures verified through the batch verifier.")
        self.verify_sharded = r.counter(  # tmlint: disable=metrics-discipline
            "consensus", "verify_sharded_total",
            "Batch-verify dispatches spread over the local devices "
            "(ops/ed25519_pallas.dispatch_chunks), by the devices used.",
            labels=("devices",))
        # (devices label = devices used by the dispatch; metrics.py cannot
        # know it without importing jax, and a devices="" dummy series
        # would poison a sum over the label)
        self.sigcache_hits = r.counter(
            "crypto", "sigcache_hits_total",
            "Vote-drain signature verifications skipped via the verified-"
            "signature cache (crypto/sigcache).")
        self.sigcache_misses = r.counter(
            "crypto", "sigcache_misses_total",
            "Vote-drain signature cache misses (verification paid).")
        # light client (light/client.py, docs/LIGHT.md)
        self.light_headers_verified = r.counter(
            "light", "headers_verified_total",
            "Headers a light client verified, by verification mode "
            "(sequential: through range_verify's windows).", labels=("mode",))
        self.light_range_fallbacks = r.counter(
            "light", "range_fallbacks_total",
            "Windows of a sequential sync re-run header by header because "
            "the range path itself failed (not because a header was "
            "refused).")
        self.light_skip_hops = r.counter(
            "light", "skip_hops_total",
            "Attempts of a skipping (bisection) sync that verified: the "
            "blocks a sync trusted on its way to its target, the target "
            "included.")
        self.light_skip_refused = r.counter(
            "light", "skip_refused_total",
            "Attempts of a skipping sync that the trusted set could not "
            "vouch for (ErrNewValSetCantBeTrusted): each moved the bisection "
            "one block down its cache or fetched a pivot.")
        self.light_skip_depth_max = r.gauge(
            "light", "skip_depth_max",
            "Deepest place in the bisection's block cache a skipping sync "
            "of this process has reached (high-water mark).")
        # device key tables (ops/ed25519_batch.KeyTable)
        self.keytable_keys_built = r.counter(
            "crypto", "keytable_keys_built_total",
            "Keys whose device comb tables were built because the table "
            "did not hold them.")
        self.keytable_build_launches = r.counter(
            "crypto", "keytable_build_launches_total",
            "Device programs launched to build those tables: one a 256-key "
            "tile of a request's missing keys.")
        self.keytable_clears = r.counter(
            "crypto", "keytable_clears_total",
            "Times a key table forgot every row because a build would have "
            "passed KeyTable.MAX_ROWS.")
        # state
        self.block_processing_time = r.histogram(
            "state", "block_processing_time",
            "Time spent processing a block (ApplyBlock).",
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2, 5))
        # batched execution plane (state/execution.py, docs/EXECUTION.md)
        self.deliver_batch_size = r.histogram(
            "abci", "deliver_batch_size",
            "Txs per batched DeliverTx chunk dispatch through the shared "
            "deliver engine (state/execution.py deliver_block_txs).",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048))
        self.abci_deliver_tx_invalid_total = r.counter(
            "abci", "deliver_tx_invalid_total",
            "DeliverTx responses with a non-OK code in applied blocks "
            "(txs that were committed but rejected by the app).")
        # mempool
        self.mempool_size = r.gauge("mempool", "size", "Number of uncommitted txs.")
        self.mempool_failed_txs = r.counter("mempool", "failed_txs", "Rejected txs.")
        # tx ingestion front door (mempool/ingest.py, docs/INGEST.md)
        self.ingest_batch_size = r.histogram(
            "mempool", "ingest_batch_size",
            "Txs per batched CheckTx dispatch through the ingest front "
            "door (mempool check_tx_batch).",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
        self.ingest_coalesced = r.counter(
            "mempool", "ingest_coalesced_total",
            "Txs that shared an ingest batch with at least one other "
            "concurrent submission (the coalescer's win counter).")
        self.ingest_txs = r.counter(
            "mempool", "ingest_txs_total",
            "Front-door tx admissions by result: ok / reject (CheckTx or "
            "mempool verdict) / shed (the rpc_tx admission gate).",
            labels=("result",))
        # evidence plane (evidence/reactor.py hardening, docs/BYZANTINE.md):
        # the reason label universe is the closed EvidenceError.REASONS
        # set, fully pre-seeded below
        self.evidence_rejected = r.counter(
            "evidence", "rejected_total",
            "Gossiped evidence rejected before pooling (scored against "
            "the delivering peer), by rejection reason.",
            labels=("reason",))
        # p2p
        self.peers = r.gauge("p2p", "peers", "Number of connected peers.")
        self.peer_receive_bytes = r.counter(
            "p2p", "peer_receive_bytes_total", "Bytes received.", labels=("chID",))
        self.peer_send_bytes = r.counter(
            "p2p", "peer_send_bytes_total", "Bytes sent.", labels=("chID",))
        # overload-resilience plane (utils/peerscore.py, docs/OVERLOAD.md)
        self.peer_score = r.gauge(
            "p2p", "peer_score",
            "Decaying per-peer misbehavior score (peerscore board).",
            labels=("peer",))
        self.peers_banned = r.counter(
            "p2p", "peers_banned_total",
            "Peers banned by the misbehavior scoreboard (re-offenses "
            "count again).")
        self.shed = r.counter(
            "p2p", "shed_total",
            "Messages/requests shed under overload, by channel class "
            "(consensus gossip priorities + the rpc_tx admission gate).",
            labels=("channel",))
        self.rate_limited = r.counter(
            "p2p", "rate_limited_total",
            "Inbound messages dropped by per-peer per-channel ceilings.",
            labels=("peer", "channel"))
        # robustness / chaos (no reference analogue: the fault-injection
        # layer, nemesis link plane, device breaker, and stall watchdog
        # are this tree's own; chaos runs must be visible on /metrics)
        self.consensus_stalled = r.gauge(
            "consensus", "stalled",
            "1 while the stall watchdog sees no commit progress.")
        self.watchdog_recoveries = r.counter(
            "consensus", "watchdog_recoveries_total",
            "Stall-watchdog hand-backs to fast-sync catchup.")
        # chaos counters: label sets are bounded by CANONICAL_SITES x the
        # fault-action table, but which (site, action) pairs exist depends
        # on the TMTPU_FAULTS/TMTPU_NEMESIS schedule — series appear when
        # the sampler copies faults.snapshot(), and a chaos-free node
        # correctly scrapes none.
        self.fault_site_hits = r.counter(  # tmlint: disable=metrics-discipline
            "faults", "site_hits_total",
            "Hits at rule-bearing fault sites (utils/faults.py).",
            labels=("site",))
        self.faults_fired = r.counter(  # tmlint: disable=metrics-discipline
            "faults", "fired_total",
            "Fault-rule firings by site and action.",
            labels=("site", "action"))
        self.nemesis_fired = r.counter(  # tmlint: disable=metrics-discipline
            "nemesis", "fired_total",
            "Nemesis link-plane firings by site and action "
            "('cut' = partition).", labels=("site", "action"))
        # self-healing storage plane (store/envelope.py, store/scrub.py,
        # store/repair.py, docs/DURABILITY.md): label universe is the
        # closed store table (envelope.STORES), fully pre-seeded below
        self.store_corruption_detected = r.counter(
            "store", "corruption_detected_total",
            "Store records that failed an integrity check (CRC envelope "
            "or guarded decode), by store.", labels=("store",))
        self.store_corruption_repaired = r.counter(
            "store", "corruption_repaired_total",
            "Corrupt store records healed (peer re-fetch + batch-verified "
            "rewrite, state rebuild, reindex, or quarantine-is-repair).",
            labels=("store",))
        self.store_scrub_runs = r.counter(
            "store", "scrub_runs_total",
            "Completed scrub passes (startup + unsafe_scrub RPC).")
        self.breaker_open = r.gauge(
            "ops", "breaker_open",
            "1 while the kernel's device circuit breaker is open.",
            labels=("kernel",))
        self.breaker_trips = r.gauge(
            "ops", "breaker_trips_total",
            "Lifetime closed->open transitions of the device breaker.",
            labels=("kernel",))
        # pre-seed the unlabeled watchdog + sigcache series so a healthy
        # node scrapes an explicit 0 instead of an absent metric
        self.consensus_stalled.set(0.0)
        self.watchdog_recoveries.add(0.0)
        self.sigcache_hits.add(0.0)
        self.sigcache_misses.add(0.0)
        # ...and the overload counters: a node that never sheds or bans
        # must scrape explicit zeros (dashboards alert on absence)
        self.peers_banned.add(0.0)
        for ch in ("vote", "proposal", "block_part", "rpc_tx"):
            self.shed.add(0.0, channel=ch)
        self.rate_limited.add(0.0, peer="", channel="")
        for mode in ("sequential", "skipping"):
            self.light_headers_verified.add(0.0, mode=mode)
        self.light_range_fallbacks.add(0.0)
        self.light_skip_hops.add(0.0)
        self.light_skip_refused.add(0.0)
        self.light_skip_depth_max.set(0.0)
        self.keytable_keys_built.add(0.0)
        self.keytable_build_launches.add(0.0)
        self.keytable_clears.add(0.0)
        # ingest front door: the result label universe is closed by
        # construction (docs/INGEST.md), seed it fully; the batch-size
        # histogram scrapes explicit zeros like the phase histogram
        self.ingest_batch_size.seed()
        self.ingest_coalesced.add(0.0)
        self.deliver_batch_size.seed()
        self.abci_deliver_tx_invalid_total.add(0.0)
        for result in ("ok", "reject", "shed"):
            self.ingest_txs.add(0.0, result=result)
        # evidence rejections: closed reason universe (types/evidence.py
        # EvidenceError.REASONS), a node that never sees junk evidence
        # scrapes explicit zeros
        from tendermint_tpu.types.evidence import EvidenceError as _EvErr

        for reason in _EvErr.REASONS:
            self.evidence_rejected.add(0.0, reason=reason)
        # p2p byte counters follow the same convention (chID values are
        # bounded by the node's channel table, first traffic creates them)
        self.peer_receive_bytes.add(0.0, chID="")
        self.peer_send_bytes.add(0.0, chID="")
        # the storage-plane counters' label universe IS envelope.STORES
        from tendermint_tpu.store.envelope import STORES as _stores

        self.store_scrub_runs.add(0.0)
        for store in _stores:
            self.store_corruption_detected.add(0.0, store=store)
            self.store_corruption_repaired.add(0.0, store=store)
        # the device-breaker pair has a two-kernel label universe: seed it
        # fully so "breaker never tripped" is an explicit 0, not absence
        for kernel in ("ed25519", "sr25519"):
            self.breaker_open.set(0.0, kernel=kernel)
            self.breaker_trips.set(0.0, kernel=kernel)
        # which route answered a batch: the closed set dispatch_batch
        # chooses from, seeded so "the Pallas route never ran" is a zero
        from tendermint_tpu.ops.breaker import ROUTES as _routes

        for route in _routes:
            self.batch_verify_seconds.seed(route=route)
        # the phase histogram's label universe IS trace.MIRRORED_SPANS:
        # seed every series so dashboards see zeros, not absence, and the
        # scrape-shape test can pin the full exposition
        from tendermint_tpu.utils import trace as _tmtrace

        for phase in _tmtrace.MIRRORED_SPANS:
            self.trace_phase_seconds.seed(phase=phase)
        # consensus.step spans mirror into the per-step histogram too
        # (state_machine tags the step NAME); seed the exact universe the
        # machine labels with, so a step added to cstypes cannot drift
        from tendermint_tpu.consensus.cstypes import STEP_NAMES

        for step_name in STEP_NAMES.values():
            self.step_duration.seed(step=step_name)


# Global registry hook for hot paths that have no handle on the node (the
# batch verifier). None until a node enables instrumentation.
GLOBAL_NODE_METRICS: NodeMetrics | None = None


class MetricsServer:
    """reference: node/node.go:1219 startPrometheusServer."""

    def __init__(self, registry: Registry, addr: str):
        host, port = addr.rsplit(":", 1) if ":" in addr else ("", addr)
        registry_ref = registry

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                if self.path.rstrip("/") not in ("", "/metrics"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = registry_ref.expose().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        self._httpd = ThreadingHTTPServer((host or "0.0.0.0", int(port)), Handler)
        self.addr = f"{self._httpd.server_address[0]}:{self._httpd.server_address[1]}"
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="prometheus", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
