"""Standing benchmark suite: the BASELINE configs + the north-star
20,480-sig commit verify.

Prints ONE JSON line on stdout:

    {"metric", "value", "unit", "vs_baseline", "configs": {...}}

where value/vs_baseline are the headline 20,480-sig commit p50 (ms) and
`configs` carries one entry per BASELINE.json config. Diagnostics and the
per-config table go to stderr (the artifact model is the reference's
docs/qa/v034/README.md standing QA tables).

Measurement discipline (host-clock medians on a shared host are fragile —
any concurrent process poisons a round):

 * A fixed CPU spin is timed before every round; a round whose spin is
   >1.3x the best spin observed is CONTENDED and retried (up to 2 extras).
 * The recorded statistic is the median of round p50s when the spread
   across rounds is <=1.3x, else the MIN (min-of-rounds is the honest
   quiet-host number; medians of poisoned rounds measure the contention,
   not the code).
 * The sync floor (a trivial 1-element op round trip) and host-prep
   decomposition are printed so the fixed environment latency is never
   conflated with marginal throughput.

vs_baseline = speedup vs the reference's serial CPU anchor for the same
work (Go x/crypto ed25519 / go-schnorrkel ~= 85 us/sig/core; BASELINE.md
crypto row).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

N_SIGS = int(os.environ.get("BENCH_N_SIGS", 20480))
ITERS = int(os.environ.get("BENCH_ITERS", 5))
ROUNDS = int(os.environ.get("BENCH_ROUNDS", 3))
MAX_RETRY_ROUNDS = int(os.environ.get("BENCH_MAX_RETRY", 2))
N_RANGE_HEADERS = int(os.environ.get("BENCH_RANGE_HEADERS", 10000))
BASELINE_US_PER_SIG = 85.0
SPREAD_LIMIT = 1.3

BENCH_CHAIN = "bench-chain"


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _spin_ms() -> float:
    """Fixed CPU workload -> elapsed ms; inflation == host contention.
    Shared with the e2e runner's load-scaled progress waits."""
    from tendermint_tpu.e2e.runner import _spin_ms as probe

    return probe()


def _measure(fn, iters):
    times = []
    for _ in range(iters):
        t0 = time.monotonic()
        fn()
        times.append(time.monotonic() - t0)
    return times


class Rounds:
    """Contention-aware repeated measurement of one benchmark closure."""

    def __init__(self):
        self.best_spin = min(_spin_ms() for _ in range(3))

    def run(self, fn, iters=ITERS, rounds=ROUNDS, warmup_rounds=0,
            report=None, pre_round=None):
        """report="min" always records min-of-rounds (the honest quiet-host
        number for configs whose long iterations make contended rounds
        likely); default is the headline policy (median, min under spread).
        warmup_rounds: full measured-and-discarded rounds before recording
        (settles page cache/allocator/JIT state beyond the single
        throwaway call). pre_round: hook run OUTSIDE the timed region before
        every round (e.g. gc.collect, so a generational collection triggered
        by accumulated garbage cannot land inside a timed iteration)."""
        fn()  # throwaway: settle allocator/page-cache state after generation
        for _ in range(warmup_rounds):
            _measure(fn, iters)
        p50s, spins, retries = [], [], 0
        while len(p50s) < rounds:
            if pre_round is not None:
                pre_round()
            # Spin BEFORE and AFTER: contention that starts mid-round would
            # otherwise slip past a leading-only check.
            spin_a = _spin_ms()
            self.best_spin = min(self.best_spin, spin_a)
            times = _measure(fn, iters)
            spin_b = _spin_ms()
            self.best_spin = min(self.best_spin, spin_b)
            spin = max(spin_a, spin_b)
            p50 = statistics.median(times) * 1e3
            if (spin > SPREAD_LIMIT * self.best_spin
                    and retries < MAX_RETRY_ROUNDS):
                retries += 1
                _log(f"#   contended round discarded (spin {spin:.1f}ms vs "
                     f"best {self.best_spin:.1f}ms), retrying")
                continue
            p50s.append(p50)
            spins.append(round(spin, 1))
        spread = max(p50s) / min(p50s)
        if report == "min" or spread > SPREAD_LIMIT:
            value = min(p50s)
        else:
            value = statistics.median(p50s)
        return value, dict(rounds_ms=[round(p, 1) for p in p50s],
                           spread=round(spread, 2), spins_ms=spins,
                           retries=retries)


# --------------------------------------------------------------------------
# Phase attribution (docs/OBSERVABILITY.md): where does a decision's wall
# time go, from the production spans of the flight recorder?
# --------------------------------------------------------------------------


def _span_phases_us(agg: dict) -> dict:
    """Tracer aggregation -> canonical phase table (us). The device phase
    is folded into readback on the production spans (the host blocks in
    _device_get until the kernel finishes); device time proper comes from
    the profiler trace of a benchmark/run.py --trace 1 run."""
    def us(name):
        return agg.get(name, {}).get("total_s", 0.0) * 1e6

    return {"host_prep": round(us("verify.host_prep"), 1),
            "queue": round(us("verify.queue"), 1),
            "device": 0.0,
            "readback": round(us("verify.readback"), 1),
            "replay": round(us("verify.replay"), 1)}


# --------------------------------------------------------------------------
# Workload generators
# --------------------------------------------------------------------------


def _gen_flat_commit(n_sigs: int):
    """Synthetic n_sigs/2-validator commit (prevote+precommit rounds),
    unique keys, canonical-vote-sized messages."""
    from tendermint_tpu.crypto import ed25519 as ref

    n_vals = n_sigs // 2
    privs = [ref.gen_priv_key(i.to_bytes(4, "big") * 8) for i in range(n_vals)]
    items = []
    for r in range(2):
        for i in range(n_vals):
            msg = (b"\x08\x02\x11" + (12345).to_bytes(8, "little")
                   + b"\x19" + r.to_bytes(8, "little")
                   + b"\x22\x48" + bytes(72) + b"bench-chain" + i.to_bytes(4, "big"))
            items.append((privs[i].pub_key().data, msg, ref.sign(privs[i].data, msg)))
    return items


def _mk_valset(n_ed: int, n_sr: int = 0, power: int = 10):
    from tendermint_tpu.crypto import ed25519, sr25519
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    privs = [ed25519.gen_priv_key((i + 1).to_bytes(4, "big") * 8)
             for i in range(n_ed)]
    privs += [sr25519.gen_priv_key((i + 1).to_bytes(4, "big"))
              for i in range(n_sr)]
    vals = ValidatorSet([Validator.new(p.pub_key(), power) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    privs = [by_addr[v.address] for v in vals.validators]
    return privs, vals


def _sign_commit_bid(bid, height, ts, vals, privs, chain_id=BENCH_CHAIN):
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, PRECOMMIT_TYPE, Vote

    sigs = []
    for i, (priv, val) in enumerate(zip(privs, vals.validators)):
        vote = Vote(type=PRECOMMIT_TYPE, height=height, round=1,
                    block_id=bid, timestamp=ts,
                    validator_address=val.address, validator_index=i)
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address, ts,
                              priv.sign(vote.sign_bytes(chain_id))))
    return Commit(height=height, round=1, block_id=bid, signatures=sigs)


def _sign_commit(header, vals, privs, chain_id=BENCH_CHAIN):
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.ttime import Time

    bid = BlockID(hash=header.hash(),
                  part_set_header=PartSetHeader(total=1, hash=b"\xcd" * 32))
    return _sign_commit_bid(bid, header.height, Time(header.time.seconds, 0),
                            vals, privs, chain_id)


def _gen_light_chain(n_headers: int, n_vals: int):
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader
    from tendermint_tpu.types.ttime import Time

    privs, vals = _mk_valset(n_vals)
    out = []
    last_bid = BlockID()
    t0 = 1_700_000_000
    for h in range(1, n_headers + 1):
        header = Header(
            chain_id=BENCH_CHAIN, height=h, time=Time(t0 + 10 * h, 0),
            last_block_id=last_bid,
            validators_hash=vals.hash(), next_validators_hash=vals.hash(),
            proposer_address=vals.validators[0].address,
        )
        commit = _sign_commit(header, vals, privs)
        out.append(LightBlock(signed_header=SignedHeader(header, commit),
                              validator_set=vals.copy()))
        last_bid = commit.block_id
    return out


# --------------------------------------------------------------------------
# Configs
# --------------------------------------------------------------------------


def config_batch64(rr, items64):
    """BASELINE config 1: 64-sig batch latency (kernel MIN_BUCKET)."""
    from tendermint_tpu.ops import ed25519_batch

    assert ed25519_batch.verify_batch(items64).all()
    value, detail = rr.run(lambda: ed25519_batch.verify_batch(items64))
    base = BASELINE_US_PER_SIG * 64 / 1000.0
    return dict(metric="batch64_p50_ms", value=round(value, 2), unit="ms",
                vs_baseline=round(base / value, 2), **detail)


def config_commit150(rr):
    """BASELINE config 2: 150-validator commit (Cosmos-Hub-4 scale) through
    the production ValidatorSet.verify_commit path."""
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.ttime import Time

    privs, vals = _mk_valset(150)
    header = Header(chain_id=BENCH_CHAIN, height=5, time=Time(1_700_000_050, 0),
                    last_block_id=BlockID(), validators_hash=vals.hash(),
                    next_validators_hash=vals.hash(),
                    proposer_address=vals.validators[0].address)
    commit = _sign_commit(header, vals, privs)

    def run():
        vals.verify_commit(BENCH_CHAIN, commit.block_id, 5, commit)

    run()
    value, detail = rr.run(run)
    base = BASELINE_US_PER_SIG * 150 / 1000.0
    return dict(metric="commit150_verify_p50_ms", value=round(value, 2),
                unit="ms", vs_baseline=round(base / value, 2), **detail)


def config_range_verify(rr):
    """BASELINE config 3: sequential header-range sync, one batched flush
    (light/range_verify.py) over N_RANGE_HEADERS headers."""
    from tendermint_tpu.light.range_verify import verify_header_range
    from tendermint_tpu.types.ttime import Time

    t0 = time.monotonic()
    chain = _gen_light_chain(N_RANGE_HEADERS, 1)
    gen_s = time.monotonic() - t0
    trusted = chain[0]
    rest = chain[1:]
    now = Time(1_700_000_000 + 10 * (N_RANGE_HEADERS + 2), 0)

    def run():
        # Trusting period spans the whole generated range (the reference
        # default for light sync is weeks; the 10s header cadence here
        # covers ~28h for 10k headers).
        verify_header_range(trusted, rest, 14 * 86400.0, now)

    # Stability (this config's rounds spread widest): the same
    # discipline as the headline config -- full ITERS so one GC/contention
    # spike cannot poison a round's median (with iters=2 the "median" was a
    # mean of two), full ROUNDS behind the contended-round retry, plus one
    # measured-and-discarded warmup round to settle page cache + keyset
    # state, gc.collect between rounds (10k LightBlocks of garbage otherwise
    # trip gen-2 collections mid-iteration), and min-of-rounds as the
    # recorded quiet-host number.
    import gc

    value, detail = rr.run(run, iters=ITERS, rounds=ROUNDS,
                           warmup_rounds=1, report="min",
                           pre_round=gc.collect)
    n = len(rest)
    base = BASELINE_US_PER_SIG * n / 1000.0  # 1 sig/header serial anchor
    return dict(metric=f"range_verify_{n}_headers_p50_ms",
                value=round(value, 1), unit="ms",
                vs_baseline=round(base / value, 2),
                us_per_header=round(value * 1e3 / n, 2),
                gen_s=round(gen_s, 1), report="min", **detail)


def config_mixed_commit(rr):
    """BASELINE config 4 (fast-sync replay at 1000 validators, mixed
    ed25519/sr25519): per-block commit-verify cost through the production
    verify_commit path with a 700/300 ed25519/sr25519 set."""
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.ttime import Time

    t0 = time.monotonic()
    privs, vals = _mk_valset(700, 300)
    header = Header(chain_id=BENCH_CHAIN, height=9, time=Time(1_700_000_090, 0),
                    last_block_id=BlockID(), validators_hash=vals.hash(),
                    next_validators_hash=vals.hash(),
                    proposer_address=vals.validators[0].address)
    commit = _sign_commit(header, vals, privs)
    gen_s = time.monotonic() - t0

    def run():
        vals.verify_commit(BENCH_CHAIN, commit.block_id, 9, commit)

    run()  # warm (compiles the sr25519 kernel bucket on first ever run)
    value, detail = rr.run(run, iters=max(3, ITERS - 2))
    base = BASELINE_US_PER_SIG * 1000 / 1000.0
    return dict(metric="mixed_commit_1000v_700ed_300sr_p50_ms",
                value=round(value, 1), unit="ms",
                vs_baseline=round(base / value, 2),
                blocks_per_s=round(1000.0 / value, 1),
                gen_s=round(gen_s, 1), **detail)


def config_fastsync(rr):
    """BASELINE config 4 proper: fast-sync replay of mixed ed25519/sr25519
    blocks @ 1000 validators through the verify-ahead pipeline
    (blockchain/pipeline.py, driven by the shared headless replay harness
    in blockchain/replay.py), reporting blocks_per_s at depth 1 (the old
    serial loop's behavior) vs the default depth. Both depths must accept
    the same blocks and converge to the same app hash."""
    from tendermint_tpu.blockchain import pipeline as bpipe
    from tendermint_tpu.blockchain.replay import ReplayCtx, make_chain

    n_blocks = int(os.environ.get("BENCH_FASTSYNC_BLOCKS", 8))
    t0 = time.monotonic()
    privs, vals = _mk_valset(700, 300)
    # n_blocks+1 pooled blocks -> n_blocks appliable heights
    blocks = make_chain(BENCH_CHAIN, n_blocks + 1, vals, privs)
    gen_s = time.monotonic() - t0

    def run_depth(depth):
        prev = os.environ.get("TM_TPU_VERIFY_AHEAD")
        os.environ["TM_TPU_VERIFY_AHEAD"] = str(depth)
        try:
            ctx = ReplayCtx(vals, BENCH_CHAIN)
            for i, b in enumerate(blocks):
                ctx.pool.add_block("pA" if i % 2 == 0 else "pB", b)
            pipe = bpipe.VerifyAheadPipeline()
            while pipe.process_next(ctx):
                pass
            assert not ctx.punished and len(ctx.applied) == n_blocks, (
                ctx.punished, ctx.applied)
            return ctx
        finally:
            if prev is None:
                os.environ.pop("TM_TPU_VERIFY_AHEAD", None)
            else:
                os.environ["TM_TPU_VERIFY_AHEAD"] = prev

    depth_default = bpipe.DEFAULT_DEPTH
    # Correctness gate (also warms kernels/keysets for both shapes):
    # identical acceptance + app hash at depth 1 and default depth.
    ctx1, ctxd = run_depth(1), run_depth(depth_default)
    assert ctx1.applied == ctxd.applied and ctx1.app_hash == ctxd.app_hash

    v1, _ = rr.run(lambda: run_depth(1), iters=2, rounds=2, report="min")
    vd, detail = rr.run(lambda: run_depth(depth_default), iters=2, rounds=2,
                        report="min")
    bps1 = n_blocks / (v1 / 1e3)
    bpsd = n_blocks / (vd / 1e3)
    # serial CPU anchor: one core verifying the +2/3 light prefix per block
    prefix_sigs = len(vals.commit_light_prefix(
        blocks[1].last_commit, vals.total_voting_power() * 2 // 3))
    base_bps = 1e3 / (BASELINE_US_PER_SIG * prefix_sigs / 1000.0)
    return dict(metric=f"fastsync_1000v_mixed_{n_blocks}_blocks_per_s",
                value=round(bpsd, 1), unit="blocks/s",
                vs_baseline=round(bpsd / base_bps, 2),
                depth1_blocks_per_s=round(bps1, 1),
                speedup_vs_depth1=round(bpsd / bps1, 2),
                depth=depth_default, prefix_sigs=prefix_sigs,
                gen_s=round(gen_s, 1), **detail)


def config_sr25519(rr):
    """A standalone sr25519 number. Pure sr25519
    1000-validator commit through the production verify_commit path
    (reference verifies these serially via go-schnorrkel,
    crypto/sr25519/pubkey.go:10)."""
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.ttime import Time

    t0 = time.monotonic()
    privs, vals = _mk_valset(0, 1000)
    header = Header(chain_id=BENCH_CHAIN, height=11, time=Time(1_700_000_110, 0),
                    last_block_id=BlockID(), validators_hash=vals.hash(),
                    next_validators_hash=vals.hash(),
                    proposer_address=vals.validators[0].address)
    commit = _sign_commit(header, vals, privs)
    gen_s = time.monotonic() - t0

    def run():
        vals.verify_commit(BENCH_CHAIN, commit.block_id, 11, commit)

    run()
    value, detail = rr.run(run, iters=max(3, ITERS - 2))
    base = BASELINE_US_PER_SIG * 1000 / 1000.0
    return dict(metric="sr25519_1000v_commit_p50_ms", value=round(value, 1),
                unit="ms", vs_baseline=round(base / value, 2),
                us_per_sig=round(value, 1),
                gen_s=round(gen_s, 1), **detail)


def config_addvote(rr):
    """BASELINE config 5: the addVote hot loop — gossiped votes at a
    1024-validator height drained through VoteSet.add_votes (one batched
    flush + in-order side effects)."""
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.ttime import Time
    from tendermint_tpu.types.vote import PREVOTE_TYPE, Vote
    from tendermint_tpu.types.vote_set import VoteSet

    privs, vals = _mk_valset(1024)
    bid = BlockID(hash=b"\x11" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
    votes = []
    for i, p in enumerate(privs):
        v = Vote(type=PREVOTE_TYPE, height=1, round=0, block_id=bid,
                 timestamp=Time(1_700_001_000, 0),
                 validator_address=vals.validators[i].address,
                 validator_index=i)
        v.signature = p.sign(v.sign_bytes(BENCH_CHAIN))
        votes.append(v)

    def run():
        vs = VoteSet(BENCH_CHAIN, 1, 0, PREVOTE_TYPE, vals)
        results = vs.add_votes(votes)
        assert all(a for a, _ in results)

    # The drain metric must keep measuring VERIFICATION: with the global
    # sigcache on, iteration 2+ would re-deliver already-verified triples
    # and time SHA-256 lookups instead of the kernel (incomparable with the
    # pre-cache trajectory). Pin the cache off for the headline number, then
    # record the cache-hit drain rate separately -- that IS the gossip
    # re-delivery speedup the cache exists for.
    from tendermint_tpu.crypto import sigcache

    from tendermint_tpu.utils import trace as tmtrace

    prev = os.environ.get("TM_TPU_SIGCACHE")
    os.environ["TM_TPU_SIGCACHE"] = "0"
    try:
        run()
        value, detail = rr.run(run, iters=max(3, ITERS - 2))
        # Phase attribution: one instrumented drain through the PRODUCTION
        # dispatch()/resolve() spans; whatever the phases don't cover is
        # the serial vote-apply replay (side effects, maj23 bookkeeping).
        tr = tmtrace.Tracer(name="bench-addvote", cap=65536, enabled=True)
        try:
            with tr.activate():
                t0 = time.monotonic()
                run()
                drain_wall_us = (time.monotonic() - t0) * 1e6
        finally:
            # a mid-drain failure must not pin the process-global ENABLED
            # flag (every later config would silently pay the traced path)
            tr.disable()
        phases_us = _span_phases_us(tr.summarize())
        p50_us = value * 1e3
        attribution = {
            "phases_us": phases_us,
            "pct_of_p50": {k: round(100.0 * v / p50_us, 1)
                           for k, v in phases_us.items()},
            "apply_us": round(max(drain_wall_us - sum(phases_us.values()),
                                  0.0), 1),
            "wall_ms": round(drain_wall_us / 1e3, 1),
        }
        # Tracing tax (ISSUE 10 bench hygiene): the SAME drain with the
        # flight recorder enabled vs disabled, both measured back to back
        # under the IDENTICAL policy (iters/rounds/min) — comparing the
        # headline median against a traced min would systematically
        # underestimate the tax. Recorded so a future PR cannot silently
        # make tracing expensive.
        ovh_iters, ovh_rounds = max(3, ITERS - 2), 2
        base_value, _ = rr.run(run, iters=ovh_iters, rounds=ovh_rounds,
                               report="min")
        tr2 = tmtrace.Tracer(name="bench-addvote-ovh", cap=65536,
                             enabled=True)
        try:
            with tr2.activate():
                traced_value, _ = rr.run(run, iters=ovh_iters,
                                         rounds=ovh_rounds, report="min")
        finally:
            tr2.disable()
        trace_overhead_pct = round(
            100.0 * (traced_value - base_value) / base_value, 2)
    finally:
        if prev is None:
            os.environ.pop("TM_TPU_SIGCACHE", None)
        else:
            os.environ["TM_TPU_SIGCACHE"] = prev
    sigcache.reset()
    run()  # populates the cache
    cached_ms, _ = rr.run(run, iters=max(3, ITERS - 2), rounds=2,
                          report="min")
    sigcache.reset()
    votes_per_s = len(votes) / (value / 1e3)
    base = BASELINE_US_PER_SIG * len(votes) / 1000.0
    return dict(metric="addvote_1024v_drain_p50_ms", value=round(value, 1),
                unit="ms", vs_baseline=round(base / value, 2),
                votes_per_s=int(votes_per_s),
                sigcache_hit_p50_ms=round(cached_ms, 1),
                sigcache_hit_votes_per_s=int(len(votes) / (cached_ms / 1e3)),
                phase_attribution=attribution,
                trace_overhead_pct=trace_overhead_pct,
                **detail)


def config_concurrent_verify(rr):
    """ISSUE 11 acceptance: M simultaneous verify paths — the consensus
    vote drain, the fast-sync commit-verify primitive, and light range
    verification — hammering the device CONCURRENTLY, with the
    continuous-batching verify service on vs off (TMTPU_VERIFY_SERVICE=0).

    The service's whole claim is that N concurrent callers share kernel
    launches (one sync floor, not N), so the reported numbers are the
    aggregate decisions/s of the storm, each path's per-decision p50, the
    service's coalescing stats, and the flight-recorder phase attribution
    per path for BOTH sides — the win must show up as the per-decision
    readback/host_prep share shrinking, not just a better total."""
    import threading

    from tendermint_tpu.crypto import sigcache, verify_service
    from tendermint_tpu.light.range_verify import verify_header_range
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.ttime import Time
    from tendermint_tpu.types.vote import PREVOTE_TYPE, Vote
    from tendermint_tpu.types.vote_set import VoteSet
    from tendermint_tpu.utils import trace as tmtrace

    iters_per_path = int(os.environ.get("BENCH_CONCURRENT_ITERS", 4))
    t0 = time.monotonic()
    # drain path: 512-validator prevote pile through VoteSet.add_votes
    d_privs, d_vals = _mk_valset(512)
    d_bid = BlockID(hash=b"\x31" * 32,
                    part_set_header=PartSetHeader(total=1, hash=b"\x32" * 32))
    d_votes = []
    for i, p in enumerate(d_privs):
        v = Vote(type=PREVOTE_TYPE, height=1, round=0, block_id=d_bid,
                 timestamp=Time(1_700_002_000, 0),
                 validator_address=d_vals.validators[i].address,
                 validator_index=i)
        v.signature = p.sign(v.sign_bytes(BENCH_CHAIN))
        d_votes.append(v)
    # fastsync path: 512-validator commit through verify_commit_light
    f_privs, f_vals = _mk_valset(512, power=7)
    f_header = Header(chain_id=BENCH_CHAIN, height=13,
                      time=Time(1_700_002_100, 0), last_block_id=BlockID(),
                      validators_hash=f_vals.hash(),
                      next_validators_hash=f_vals.hash(),
                      proposer_address=f_vals.validators[0].address)
    f_commit = _sign_commit(f_header, f_vals, f_privs)
    # range path: light header chain (BASELINE config 3 shape, small)
    r_headers = int(os.environ.get("BENCH_CONCURRENT_RANGE_HEADERS", 192))
    r_chain = _gen_light_chain(r_headers, 4)
    r_trusted, r_rest = r_chain[0], r_chain[1:]
    r_now = Time(1_700_000_000 + 10 * (r_headers + 2), 0)
    gen_s = time.monotonic() - t0

    def drain_decision():
        vs = VoteSet(BENCH_CHAIN, 1, 0, PREVOTE_TYPE, d_vals)
        results = vs.add_votes(d_votes)
        assert all(a for a, _ in results)

    def fastsync_decision():
        f_vals.verify_commit_light(BENCH_CHAIN, f_commit.block_id, 13,
                                   f_commit)

    def range_decision():
        verify_header_range(r_trusted, r_rest, 14 * 86400.0, r_now)

    paths = (("drain", drain_decision), ("fastsync", fastsync_decision),
             ("range", range_decision))

    def storm(collect=None):
        """One concurrent pass: every path runs iters_per_path decisions on
        its own thread. collect[path] <- per-decision wall times."""
        barrier = threading.Barrier(len(paths))
        errors = []

        def worker(name, fn, tracer):
            try:
                if tracer is not None:
                    stack = tracer.activate()
                    stack.__enter__()
                barrier.wait()
                for _ in range(iters_per_path):
                    t = time.monotonic()
                    fn()
                    if collect is not None:
                        collect[name].append(time.monotonic() - t)
                if tracer is not None:
                    stack.__exit__(None, None, None)
            except Exception as e:  # noqa: BLE001 - surfaced after join
                errors.append((name, e))

        tracers = {name: (tmtrace.Tracer(name=f"bench-cv-{name}", cap=65536,
                                         enabled=True)
                          if collect is not None else None)
                   for name, _ in paths}
        threads = [threading.Thread(target=worker, args=(n, f, tracers[n]))
                   for n, f in paths]
        t = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.monotonic() - t
        for tr in tracers.values():
            if tr is not None:
                tr.disable()
        if errors:
            raise RuntimeError(f"concurrent_verify path failed: {errors}")
        return wall, tracers

    def measure(service_on):
        prev = os.environ.get("TMTPU_VERIFY_SERVICE")
        os.environ["TMTPU_VERIFY_SERVICE"] = "1" if service_on else "0"
        verify_service.reset()
        try:
            storm()  # warm kernels/keysets for this routing
            walls = []
            collect = {n: [] for n, _ in paths}
            tracers = None
            for _ in range(2):
                w, trs = storm(collect=collect)
                walls.append(w)
                tracers = trs
            svc = verify_service.get()
            phases = {n: _span_phases_us(tracers[n].summarize())
                      for n, _ in paths}
            return dict(
                wall_s=min(walls),
                agg_decisions_per_s=(len(paths) * iters_per_path * 2
                                     / sum(walls)),
                per_path_p50_ms={n: round(statistics.median(ts) * 1e3, 1)
                                 for n, ts in collect.items()},
                # per-decision phases: `tracers` holds the LAST storm's
                # fresh Tracer objects, so totals cover iters_per_path
                # decisions (NOT both storms)
                phase_attribution={
                    n: {k: round(v / iters_per_path, 1)
                        for k, v in phases[n].items()}
                    for n, _ in paths},
                service=dict(launches=svc.launches, requests=svc.requests,
                             max_coalesced=svc.max_coalesced,
                             fallbacks=svc.fallbacks),
            )
        finally:
            if prev is None:
                os.environ.pop("TMTPU_VERIFY_SERVICE", None)
            else:
                os.environ["TMTPU_VERIFY_SERVICE"] = prev
            verify_service.reset()

    prev_sc = os.environ.get("TM_TPU_SIGCACHE")
    os.environ["TM_TPU_SIGCACHE"] = "0"  # keep every decision VERIFYING
    try:
        on = measure(True)
        off = measure(False)
    finally:
        if prev_sc is None:
            os.environ.pop("TM_TPU_SIGCACHE", None)
        else:
            os.environ["TM_TPU_SIGCACHE"] = prev_sc
        sigcache.reset()
    speedup = on["agg_decisions_per_s"] / max(off["agg_decisions_per_s"],
                                              1e-9)
    return dict(metric="concurrent_verify_3path_agg_decisions_per_s",
                value=round(on["agg_decisions_per_s"], 2),
                unit="decisions/s",
                vs_baseline=round(speedup, 2),
                speedup_vs_service_off=round(speedup, 2),
                service_off_decisions_per_s=round(
                    off["agg_decisions_per_s"], 2),
                per_path_p50_ms_on=on["per_path_p50_ms"],
                per_path_p50_ms_off=off["per_path_p50_ms"],
                phase_attribution_on=on["phase_attribution"],
                phase_attribution_off=off["phase_attribution"],
                service_stats=on["service"],
                iters_per_path=iters_per_path, gen_s=round(gen_s, 1))


def config_light_serve(rr):
    """ISSUE 20 acceptance: gateway light-serving throughput. C concurrent
    clients chase the tip of a signed header chain through ONE shared
    LightGateway (verified-answer cache + single-flight coalescing: ~H
    verifications total) vs the SAME workload where every client runs its
    own light client and verifies everything itself (serial: C*H
    verifications). Reports aggregate queries/s, p99 serve latency, the
    coalesced-vs-serial speedup, and the verify-service on/off delta.
    Sigcache is pinned OFF so the serial baseline actually re-verifies."""
    import threading

    from tendermint_tpu.crypto import sigcache, verify_service
    from tendermint_tpu.light.client import Client, TrustOptions
    from tendermint_tpu.light.gateway import LightGateway
    from tendermint_tpu.light.provider import MockProvider
    from tendermint_tpu.light.store import DBStore
    from tendermint_tpu.store.db import MemDB
    from tendermint_tpu.types.ttime import Time

    n_headers = int(os.environ.get("BENCH_LIGHT_HEADERS", 32))
    n_clients = int(os.environ.get("BENCH_LIGHT_CLIENTS", 8))
    t0 = time.monotonic()
    chain = _gen_light_chain(n_headers, 16)
    gen_s = time.monotonic() - t0
    lbs = {lb.height: lb for lb in chain}
    now = Time(1_700_000_000 + 10 * (n_headers + 2), 0)
    period_s = 14 * 86400.0
    opts = TrustOptions(period_s=period_s, height=1, hash=chain[0].hash())

    def crowd(worker):
        """C threads running `worker(client_index, latencies)`; returns
        (wall_s, all latencies)."""
        lat: list[list[float]] = [[] for _ in range(n_clients)]
        errors: list = []

        def run(c):
            try:
                worker(c, lat[c])
            except Exception as e:  # noqa: BLE001 - surfaced after join
                errors.append(e)

        threads = [threading.Thread(target=run, args=(c,))
                   for c in range(n_clients)]
        t = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.monotonic() - t
        if errors:
            raise RuntimeError(f"light_serve worker failed: {errors}")
        return wall, [x for per in lat for x in per]

    def pass_gateway():
        gw = LightGateway(BENCH_CHAIN, opts,
                          [MockProvider(BENCH_CHAIN, lbs) for _ in range(3)],
                          DBStore(MemDB(), BENCH_CHAIN),
                          sleep=lambda s: None)

        def worker(c, out):
            for h in range(2, n_headers + 1):
                t = time.monotonic()
                lb, _verdict = gw.serve_light_block(h, now=now)
                out.append(time.monotonic() - t)
                assert lb.height == h

        return crowd(worker)

    def pass_serial():
        def worker(c, out):
            client = Client(BENCH_CHAIN, opts,
                            MockProvider(BENCH_CHAIN, lbs), [],
                            DBStore(MemDB(), BENCH_CHAIN))
            for h in range(2, n_headers + 1):
                t = time.monotonic()
                lb = client.verify_light_block_at_height(h, now)
                out.append(time.monotonic() - t)
                assert lb.height == h

        return crowd(worker)

    n_queries = n_clients * (n_headers - 1)

    def measure(mode_pass, service_on):
        prev = os.environ.get("TMTPU_VERIFY_SERVICE")
        os.environ["TMTPU_VERIFY_SERVICE"] = "1" if service_on else "0"
        verify_service.reset()
        try:
            mode_pass()  # warm kernels/keysets for this routing
            walls, lat = [], []
            for _ in range(2):
                w, ls = mode_pass()
                walls.append(w)
                lat = ls
            svc = verify_service.get()
            lat.sort()
            return dict(
                wall_s=min(walls),
                queries_per_s=n_queries / min(walls),
                p50_ms=round(lat[len(lat) // 2] * 1e3, 2),
                p99_ms=round(lat[min(int(len(lat) * 0.99),
                                     len(lat) - 1)] * 1e3, 2),
                launches=svc.launches, requests=svc.requests,
            )
        finally:
            if prev is None:
                os.environ.pop("TMTPU_VERIFY_SERVICE", None)
            else:
                os.environ["TMTPU_VERIFY_SERVICE"] = prev
            verify_service.reset()

    prev_sc = os.environ.get("TM_TPU_SIGCACHE")
    os.environ["TM_TPU_SIGCACHE"] = "0"
    try:
        gw_on = measure(pass_gateway, True)
        gw_off = measure(pass_gateway, False)
        serial = measure(pass_serial, True)
    finally:
        if prev_sc is None:
            os.environ.pop("TM_TPU_SIGCACHE", None)
        else:
            os.environ["TM_TPU_SIGCACHE"] = prev_sc
        sigcache.reset()
    speedup = gw_on["queries_per_s"] / max(serial["queries_per_s"], 1e-9)
    return dict(metric=f"light_serve_{n_clients}c_queries_per_s",
                value=round(gw_on["queries_per_s"], 1), unit="queries/s",
                vs_baseline=round(speedup, 2),
                speedup_vs_serial=round(speedup, 2),
                serial_queries_per_s=round(serial["queries_per_s"], 1),
                p99_serve_ms=gw_on["p99_ms"],
                p99_serve_ms_serial=serial["p99_ms"],
                service_off_queries_per_s=round(gw_off["queries_per_s"], 1),
                service_stats=dict(launches=gw_on["launches"],
                                   requests=gw_on["requests"],
                                   launches_serial=serial["launches"]),
                clients=n_clients, headers=n_headers, gen_s=round(gen_s, 1))


def config_mempool_ingest(rr):
    """ISSUE 12 acceptance: sustained front-door txs/s and p99 admission
    latency, micro-batched coalescer vs the TMTPU_INGEST=0 serial baseline,
    against a SOCKET ABCI app — each serial CheckTx pays a real round trip
    (the cost the batched RequestCheckTxBatch amortizes), exactly the shape
    of a production out-of-process app. Batch-rich load: N submitter
    threads hammering ingest_tx concurrently."""
    import threading

    from tendermint_tpu.abci import types as abci_types
    from tendermint_tpu.abci.client import ABCISocketClient
    from tendermint_tpu.abci.server import ABCIServer
    from tendermint_tpu.mempool.mempool import Mempool

    import hashlib

    n_threads = int(os.environ.get("BENCH_INGEST_THREADS", 16))
    n_txs = int(os.environ.get("BENCH_INGEST_TXS", 6000))
    per_thread = n_txs // n_threads

    class PricedApp(abci_types.Application):
        """A state-bearing app with realistic per-CALL admission cost: every
        CheckTx call opens a state context (modeled as hashing the app's
        state blob — real apps branch the store and build a gas meter per
        call), then prices each tx. Its NATIVE check_tx_batch opens ONE
        context per batch — exactly the amortization the batched ABCI seam
        exists to unlock (docs/INGEST.md)."""

        STATE = b"\x5a" * (256 * 1024)

        def _open_context(self) -> None:
            hashlib.sha256(self.STATE).digest()

        def _price(self, tx: bytes) -> abci_types.ResponseCheckTx:
            # priority from the tx tail: the v1 lanes stay exercised
            return abci_types.ResponseCheckTx(
                code=0, gas_wanted=1, priority=tx[-1] if tx else 0)

        def check_tx(self, req):
            self._open_context()
            return self._price(req.tx)

        def check_tx_batch(self, req):
            self._open_context()
            return abci_types.ResponseCheckTxBatch(
                responses=[self._price(tx) for tx in req.txs])

    server = ABCIServer(PricedApp(), "tcp://127.0.0.1:0")
    server.start()

    def measure(batched: bool) -> dict:
        prev = os.environ.get("TMTPU_INGEST")
        os.environ["TMTPU_INGEST"] = "1" if batched else "0"
        app = ABCISocketClient(server.addr)
        mp = Mempool(app, version="v1", max_txs=2 * n_txs,
                     cache_size=4 * n_txs)
        lat: list[list[float]] = [[] for _ in range(n_threads)]
        errors = []

        def worker(t):
            try:
                for i in range(per_thread):
                    tx = b"ingest-%d-%d=" % (t, i) + bytes([(t + i) % 251 + 1])
                    t0 = time.monotonic()
                    res = mp.ingest_tx(tx)
                    lat[t].append(time.monotonic() - t0)
                    assert res.is_ok()
            except Exception as e:  # noqa: BLE001 - surfaced after join
                errors.append((t, e))

        try:
            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(n_threads)]
            t0 = time.monotonic()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.monotonic() - t0
            if errors:
                raise RuntimeError(f"mempool_ingest worker failed: {errors}")
            alllat = sorted(x for ts in lat for x in ts)
            co = mp._ingest
            return dict(
                txs_per_s=len(alllat) / wall,
                p50_ms=alllat[len(alllat) // 2] * 1e3,
                p99_ms=alllat[int(0.99 * (len(alllat) - 1))] * 1e3,
                batches=co.batches, coalesced_txs=co.coalesced_txs,
                max_coalesced=co.max_coalesced)
        finally:
            app.close()
            if prev is None:
                os.environ.pop("TMTPU_INGEST", None)
            else:
                os.environ["TMTPU_INGEST"] = prev

    try:
        measure(True)  # warm sockets/allocator for both routings
        on = measure(True)
        off = measure(False)
    finally:
        server.stop()
    speedup = on["txs_per_s"] / max(off["txs_per_s"], 1e-9)
    return dict(metric="mempool_ingest_sustained_txs_per_s",
                value=round(on["txs_per_s"], 1),
                unit="txs/s",
                vs_baseline=round(speedup, 2),
                speedup_vs_serial=round(speedup, 2),
                serial_txs_per_s=round(off["txs_per_s"], 1),
                p99_admission_ms_batched=round(on["p99_ms"], 2),
                p99_admission_ms_serial=round(off["p99_ms"], 2),
                p50_admission_ms_batched=round(on["p50_ms"], 2),
                ingest_stats=dict(batches=on["batches"],
                                  coalesced_txs=on["coalesced_txs"],
                                  max_coalesced=on["max_coalesced"]),
                threads=n_threads, txs=n_txs)


def config_chain_throughput(rr):
    """ISSUE 17: end-to-end chain throughput (blocks/s) at 1000 mixed
    validators with FULL blocks, replayed through the verify-ahead
    pipeline against a socket-backed kvstore app — the batched execution
    plane (DeliverTxBatch: one ABCI wire round trip per
    TMTPU_DELIVER_MAX_BATCH chunk) vs TMTPU_DELIVER=0 (one round trip per
    tx, the old serial loop). Both modes must converge to the same replay
    app hash; the serial run is the config's own baseline
    (speedup_vs_serial)."""
    from tendermint_tpu.abci.client import ABCISocketClient
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.abci.server import ABCIServer
    from tendermint_tpu.blockchain import pipeline as bpipe
    from tendermint_tpu.blockchain.replay import ReplayCtx, make_chain

    n_blocks = int(os.environ.get("BENCH_CHAIN_BLOCKS", 6))
    txs_per_block = int(os.environ.get("BENCH_CHAIN_TXS", 512))
    t0 = time.monotonic()
    privs, vals = _mk_valset(700, 300)
    blocks = make_chain(
        BENCH_CHAIN, n_blocks + 1, vals, privs,
        txs_for=lambda h: [b"c%d-%d=%d" % (h, i, (h * 131 + i) % 9973)
                           for i in range(txs_per_block)])
    gen_s = time.monotonic() - t0

    def run(batched: bool) -> bytes:
        """One full replay: fresh app + socket per run so both modes
        apply the identical chain from genesis state."""
        prev = os.environ.get("TMTPU_DELIVER")
        os.environ["TMTPU_DELIVER"] = "1" if batched else "0"
        server = ABCIServer(KVStoreApplication(), "tcp://127.0.0.1:0")
        server.start()
        cli = None
        try:
            cli = ABCISocketClient(server.addr)
            ctx = ReplayCtx(vals, BENCH_CHAIN, app=cli)
            for i, b in enumerate(blocks):
                ctx.pool.add_block("pA" if i % 2 == 0 else "pB", b)
            pipe = bpipe.VerifyAheadPipeline()
            while pipe.process_next(ctx):
                pass
            assert not ctx.punished and len(ctx.applied) == n_blocks, (
                ctx.punished, ctx.applied)
            return ctx.app_hash
        finally:
            if cli is not None:
                cli.close()
            server.stop()
            if prev is None:
                os.environ.pop("TMTPU_DELIVER", None)
            else:
                os.environ["TMTPU_DELIVER"] = prev

    # Correctness gate (also warms kernels/keysets/allocator for both
    # modes): identical replay app hash batched vs serial.
    hb, hs = run(True), run(False)
    assert hb == hs, "batched replay app hash != serial"

    vb, detail = rr.run(lambda: run(True), iters=2, rounds=2, report="min")
    vs, _ = rr.run(lambda: run(False), iters=2, rounds=2, report="min")
    bps_b = n_blocks / (vb / 1e3)
    bps_s = n_blocks / (vs / 1e3)
    # serial CPU anchor: one core verifying the block's +2/3 light prefix
    # PLUS one socket round trip per tx (measured by the serial mode) —
    # vs_baseline for this config IS the speedup over that serial loop.
    speedup = bps_b / max(bps_s, 1e-9)
    return dict(metric=f"chain_throughput_1000v_{txs_per_block}tx_blocks_per_s",
                value=round(bps_b, 2), unit="blocks/s",
                vs_baseline=round(speedup, 2),
                speedup_vs_serial=round(speedup, 2),
                serial_blocks_per_s=round(bps_s, 2),
                txs_per_block=txs_per_block, n_blocks=n_blocks,
                gen_s=round(gen_s, 1), **detail)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tendermint_tpu.ops import ed25519_batch

    _log(f"# backend={jax.default_backend()} devices={len(jax.devices())} "
         f"loadavg={os.getloadavg()}")

    # Measure the host/kernel crossover BEFORE timing anything: the adaptive
    # routing is part of what the bench measures.
    cross = ed25519_batch.calibrate_host_crossover()
    cal = ed25519_batch._HOST_CAL
    _log(f"# crossover={cross} sigs (floor={cal['floor_ms']}ms host_rlc="
         f"{None if cal['host_us'] is None else round(cal['host_us'], 1)}us/sig)")

    t0 = time.monotonic()
    items = _gen_flat_commit(N_SIGS)
    gen_s = time.monotonic() - t0

    t0 = time.monotonic()
    out = ed25519_batch.verify_batch(items)
    warm_s = time.monotonic() - t0
    assert out.all(), "benchmark signatures must all verify"

    # Sync-latency floor of this host<->device link (trivial op + readback).
    tiny = jax.jit(lambda a: a * 2)
    np.asarray(tiny(jnp.ones((1,), jnp.int32)))
    floor_ms = min(
        _measure(lambda: np.asarray(tiny(jnp.ones((1,), jnp.int32))), 7)) * 1e3

    rr = Rounds()

    # Headline: the north-star 20,480-sig commit.
    headline, hdetail = rr.run(lambda: ed25519_batch.verify_batch(items))

    # Marginal cost with the fixed floor removed: (p50(N) - p50(N/4)) over
    # the extra signatures, both min-of-rounds. A quarter batch rides the
    # same sync floor, so the difference is pure per-signature cost.
    quarter = items[: len(items) // 4]
    ed25519_batch.verify_batch(quarter)  # build the subset keyset once
    tq, _ = rr.run(lambda: ed25519_batch.verify_batch(quarter),
                   iters=max(ITERS - 2, 3), rounds=2)
    marginal_us = max(headline - tq, 0.001) * 1e3 / (len(items) - len(quarter))

    # Host-prep decomposition (what still fights the 1 core per call).
    ks, key_idx, pub_ok = ed25519_batch.get_keyset([it[0] for it in items])
    pub_ok = pub_ok & ks.valid[key_idx]
    tprep = min(_measure(
        lambda: ed25519_batch.prepare_scalars(items, pub_ok, windows=False,
                                              reduce=False), 3)) * 1e3

    configs = {}
    failed = []
    for name, fn, args in (
        ("batch64", config_batch64, (rr, items[:64])),
        ("commit150", config_commit150, (rr,)),
        ("range_verify", config_range_verify, (rr,)),
        ("mixed_commit", config_mixed_commit, (rr,)),
        ("fastsync", config_fastsync, (rr,)),
        ("sr25519", config_sr25519, (rr,)),
        ("addvote", config_addvote, (rr,)),
        ("concurrent_verify", config_concurrent_verify, (rr,)),
        ("light_serve", config_light_serve, (rr,)),
        ("mempool_ingest", config_mempool_ingest, (rr,)),
        ("chain_throughput", config_chain_throughput, (rr,)),
    ):
        try:
            configs[name] = fn(*args)
            _log(f"# {name}: {json.dumps(configs[name])}")
        except Exception as e:  # noqa: BLE001 - one config must not kill the run
            configs[name] = dict(error=str(e))
            failed.append(name)
            _log(f"# {name}: FAILED {e}")

    baseline_ms = BASELINE_US_PER_SIG * len(items) / 1000.0
    result = {
        "metric": "ed25519_commit_verify_%d_sigs_p50" % len(items),
        "value": round(headline, 3),
        "unit": "ms",
        "vs_baseline": round(baseline_ms / headline, 2),
        "sync_floor_ms": round(floor_ms, 1),
        "marginal_us_per_sig": round(marginal_us, 2),
        "host_prep_ms": round(tprep, 1),
        "spread": hdetail["spread"],
        "configs": {k: {kk: vv for kk, vv in v.items()
                        if kk in ("metric", "value", "unit", "vs_baseline",
                                  "spread", "error", "depth1_blocks_per_s",
                                  "speedup_vs_depth1", "skipped", "devices",
                                  "single_device_marginal_us",
                                  "speedup_vs_single", "phase_attribution",
                                  "trace_overhead_pct",
                                  "speedup_vs_service_off",
                                  "service_off_decisions_per_s",
                                  "per_path_p50_ms_on",
                                  "per_path_p50_ms_off",
                                  "phase_attribution_on",
                                  "phase_attribution_off",
                                  "service_stats",
                                  "speedup_vs_serial",
                                  "serial_queries_per_s",
                                  "p99_serve_ms",
                                  "p99_serve_ms_serial",
                                  "service_off_queries_per_s",
                                  "serial_txs_per_s",
                                  "serial_blocks_per_s",
                                  "txs_per_block",
                                  "p99_admission_ms_batched",
                                  "p99_admission_ms_serial",
                                  "p50_admission_ms_batched",
                                  "ingest_stats")}
                    for k, v in configs.items()},
    }
    print(json.dumps(result))
    _log(f"# headline: rounds={hdetail['rounds_ms']}ms "
         f"spread={hdetail['spread']}x spins={hdetail['spins_ms']}ms "
         f"retries={hdetail['retries']}")
    _log(f"# gen={gen_s:.1f}s warmup={warm_s:.1f}s sync_floor={floor_ms:.1f}ms "
         f"(fixed host<->device round-trip of this link, paid once per "
         f"decision) host_prep={tprep:.1f}ms "
         f"({tprep * 1e3 / len(items):.2f}us/sig; SHA-512 in C + byte "
         f"packing; mod-L + windows now on device) "
         f"marginal={marginal_us:.2f}us/sig p50_quarter={tq:.1f}ms "
         f"({1.0 / marginal_us:.2f}M sigs/s marginal) "
         f"baseline={baseline_ms:.0f}ms")
    if failed:
        # the JSON line above carries each error; a run with one is not a pass
        sys.exit(f"bench: {len(failed)} config(s) raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
