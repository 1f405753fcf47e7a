"""ISSUE 10: the consensus flight recorder (utils/trace.py,
docs/OBSERVABILITY.md).

Four layers:

1. Tracer units: instance isolation (no cross-node interleaving), causal
   parent/child linkage + height inheritance, ring bounds, thread safety.
2. THE disabled-cost gate: with tracing off, instrumented paths must not
   touch the ring, and the hot-site guard (one attribute load) must stay
   ~free — this is what lets the spans live on per-message paths.
3. Timeline semantics: lifecycle census, causal-order verdict, phase
   aggregation, last_phase.
4. A 3-node fabric mesh smoke: a committed height's timeline contains
   every lifecycle phase exactly once, served over the unsafe_timeline
   RPC route.
"""

import json
import threading
import time
import urllib.request

import pytest

from tendermint_tpu.utils import trace

pytestmark = pytest.mark.quick


@pytest.fixture
def tracer():
    t = trace.Tracer("t-unit", cap=256, enabled=True)
    yield t
    t.disable()


# ---------------------------------------------------------------------------
# 1. tracer units
# ---------------------------------------------------------------------------


def test_instance_isolation_no_interleaving():
    """Two tracers (two fabric nodes) never see each other's spans, and
    neither pollutes the process DEFAULT ring."""
    before_default = len(trace.DEFAULT.dump())
    a = trace.Tracer("nodeA", enabled=True)
    b = trace.Tracer("nodeB", enabled=True)
    try:
        a.mark("consensus.commit", height=1)
        b.mark("consensus.proposal", height=2)
        with a.activate():
            trace.mark("consensus.precommit", height=1)
        assert [s.name for s in a.dump()] == ["consensus.commit",
                                              "consensus.precommit"]
        assert [s.name for s in b.dump()] == ["consensus.proposal"]
        assert len(trace.DEFAULT.dump()) == before_default
    finally:
        a.disable()
        b.disable()


def test_causal_parent_child_and_height_inheritance(tracer):
    with tracer.span("consensus.vote_drain", height=9, votes=3) as outer:
        with tracer.span("verify.host_prep", n=64) as inner:
            pass
        tracer.record("verify.queue", 0.002)
        tracer.mark("consensus.precommit")
        assert tracer.current_height() == 9
    assert tracer.current_height() is None
    by_name = {s.name: s for s in tracer.dump()}
    drain = by_name["consensus.vote_drain"]
    assert drain.span_id == outer and drain.parent_id == 0
    assert by_name["verify.host_prep"].span_id == inner
    # causality: children link the enclosing span and inherit its height
    for child in ("verify.host_prep", "verify.queue", "consensus.precommit"):
        assert by_name[child].parent_id == drain.span_id, child
        assert by_name[child].tags["height"] == 9, child
    # explicit height beats inheritance
    with tracer.span("fastsync.dispatch", height=5):
        tracer.mark("fastsync.apply", height=6)
    assert {s.tags["height"] for s in tracer.dump()
            if s.name == "fastsync.apply"} == {6}


def test_record_keeps_the_given_start_and_parent(tracer):
    """ISSUE 23 fault 2: record() back-dated every start from the moment of
    the call. With start= the span begins where the work began, whenever
    the record is written; without it the old back-dating holds."""
    t_began = time.monotonic() - 5.0
    tracer.record("verify.readback", 0.25, start=t_began, parent=77,
                  decision=9)
    tracer.record("verify.replay", 0.5)
    by_name = {s.name: s for s in tracer.dump()}
    rb = by_name["verify.readback"]
    assert rb.start == t_began and rb.duration_s == 0.25
    assert rb.parent_id == 77 and rb.tags == {"decision": 9}
    rp = by_name["verify.replay"]
    assert abs(rp.start - (time.monotonic() - 0.5)) < 0.1
    assert rp.parent_id == 0


def test_decision_id_is_the_root_spans_id_and_children_inherit_it(tracer):
    with tracer.span("commit.assemble", decision=True, mode="full") as did:
        assert tracer.current_decision() == did
        assert tracer.current_span() == did
        with tracer.span("verify.host_prep") as prep:
            tracer.record("verify.queue", 0.001)
            assert tracer.current_span() == prep
        tracer.annotate(sigs=3, sign_bytes_s=0.5)
    assert tracer.current_decision() == 0 and tracer.current_span() == 0
    # another thread's span names decision and parent explicitly
    with tracer.span("commit.wait", decision=did, parent=did):
        tracer.mark("consensus.precommit")
    by_name = {s.name: s for s in tracer.dump()}
    root = by_name["commit.assemble"]
    assert root.span_id == did and root.parent_id == 0
    assert root.tags == {"decision": did, "mode": "full", "sigs": 3,
                         "sign_bytes_s": 0.5}
    for name in ("verify.host_prep", "verify.queue", "commit.wait",
                 "consensus.precommit"):
        assert by_name[name].tags["decision"] == did, name
    assert by_name["verify.host_prep"].parent_id == did
    assert by_name["verify.queue"].parent_id == by_name["verify.host_prep"].span_id
    assert by_name["commit.wait"].parent_id == did


def test_spans_are_bridged_to_profiler_annotations(tracer, monkeypatch):
    """While jax is imported every span also enters a TraceAnnotation of its
    name (with the decision id); the start-up ring never does."""
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            self.what = (name, kw)

        def __enter__(self):
            seen.append(("enter",) + self.what)

        def __exit__(self, *exc):
            seen.append(("exit",) + self.what)

    monkeypatch.setattr(trace, "_ANNOTATION", Annotation)
    with tracer.span("commit.assemble", decision=True) as did:
        with tracer.span("prep.keyset"):
            pass
    trace.STARTUP.record("startup.calibrate", 0.1)
    assert seen == [
        ("enter", "commit.assemble", {"decision": did}),
        ("enter", "prep.keyset", {"decision": did}),
        ("exit", "prep.keyset", {"decision": did}),
        ("exit", "commit.assemble", {"decision": did})]


def test_startup_ring_keeps_the_outermost_jit_trace_only():
    """jax traces every jnp op inside a kernel as a function of its own,
    thousands in one kernel: only the outermost trace may reach the ring,
    or the ring is flooded before the kernel's own span arrives."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.utils import jaxcache

    jaxcache.enable()

    @jax.jit
    def inner_fn(x):
        return x + 1

    @jax.jit
    def outer_fn(x):
        return inner_fn(x) * jnp.sin(x) + inner_fn(x + 2)

    x = jnp.ones(3).block_until_ready()
    at = time.monotonic()
    outer_fn(x).block_until_ready()
    mine = [s for s in trace.STARTUP.dump() if s.start >= at]
    assert [(s.name, s.tags["fun"]) for s in mine] == [
        ("startup.jit_trace", "outer_fn"),            # the trace, once
        ("startup.jit_trace", "jit(outer_fn)"),       # its lowering to MLIR
        ("startup.jit_compile", "jit(outer_fn)")]
    # a warm call traces nothing
    at = time.monotonic()
    outer_fn(x).block_until_ready()
    assert not [s for s in trace.STARTUP.dump() if s.start >= at]


def test_startup_ring_is_always_on_and_never_raises_the_guard():
    """The start-up ring records with tracing off (a key-set miss is a cold
    path) and does not make the hot sites' guard true."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import ed25519_batch as edb

    assert trace.STARTUP.enabled
    base = trace.ENABLED
    trace.STARTUP.enable()      # no-ops: a cold ring is not counted
    trace.STARTUP.disable()
    assert trace.STARTUP.enabled and trace.ENABLED == base
    default_before = trace.DEFAULT.size()
    pubs = [ed25519.gen_priv_key(b"startup-ring-%02d" % i + bytes(17))
            .pub_key().data for i in range(3)]
    at = time.monotonic()
    ks, key_idx, pub_ok = edb.get_keyset(pubs)
    assert pub_ok.all() and len(set(key_idx)) == 3 <= ks.n_rows
    mine = [s for s in trace.STARTUP.dump() if s.start >= at]
    by_name = {s.name: s for s in mine}
    assert {"startup.key_decode", "startup.table_build"} <= set(by_name)
    assert by_name["startup.key_decode"].tags == {"keys": 3, "kind": "ed25519"}
    decode, build = by_name["startup.key_decode"], by_name["startup.table_build"]
    assert decode.start + decode.duration_s <= build.start + 1e-6
    # a second look-up of the same keys is a hit: nothing cold happened
    at = time.monotonic()
    edb.get_keyset(pubs)
    assert not [s for s in trace.STARTUP.dump() if s.start >= at
                and s.name.startswith("startup.key")]
    assert trace.ENABLED == base and trace.DEFAULT.size() == default_before


def test_ring_bound_evicts_oldest():
    t = trace.Tracer("ring", cap=16, enabled=True)
    try:
        for i in range(100):
            t.mark("consensus.commit", height=i)
        spans = t.dump()
        assert len(spans) == 16 and t.size() == 16
        assert [s.tags["height"] for s in spans] == list(range(84, 100))
    finally:
        t.disable()


def test_trace_cap_env_knob(monkeypatch):
    monkeypatch.setenv("TMTPU_TRACE_CAP", "32")
    assert trace.Tracer("capped").cap == 32
    monkeypatch.setenv("TMTPU_TRACE_CAP", "bogus")
    assert trace.Tracer("fallback").cap == trace.DEFAULT_CAP


def test_thread_safety_concurrent_recording():
    t = trace.Tracer("mt", cap=8192, enabled=True)
    errs = []

    def worker(tid):
        try:
            for i in range(200):
                with t.span("consensus.vote_drain", height=tid):
                    t.mark("consensus.commit")
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t.disable()
    assert not errs
    spans = t.dump()
    assert len(spans) == 8 * 200 * 2
    # per-thread parent stacks never crossed: every mark's parent is a
    # drain span carrying the SAME thread's height tag
    drains = {s.span_id: s for s in spans
              if s.name == "consensus.vote_drain"}
    for s in spans:
        if s.name == "consensus.commit":
            assert s.parent_id in drains
            assert drains[s.parent_id].tags["height"] == s.tags["height"]


# ---------------------------------------------------------------------------
# 2. the disabled-cost quick gate
# ---------------------------------------------------------------------------


def test_disabled_path_records_nothing_and_stays_cheap():
    """ISSUE 10 acceptance: disabled tracing costs one attribute load at
    the hot sites. Structural half: nothing touches the ring. Timing
    half: the guard pattern stays within an order of magnitude of a bare
    loop (generous bound — this catches an accidental lock/allocation on
    the disabled path, not micro-regressions)."""
    t = trace.Tracer("gate")  # disabled
    with t.span("consensus.vote_drain", height=1):
        pass
    t.mark("consensus.commit")
    t.record("verify.queue", 0.1)
    assert t.dump() == [] and not t.enabled

    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        if t.enabled:  # the documented hot-site guard
            raise AssertionError
    guard_s = time.perf_counter() - t0
    assert guard_s / n < 2e-6, f"{guard_s / n * 1e9:.0f} ns/guard"

    # the `with` form of the guard (ISSUE 23's prep.* sites): one attribute
    # load, then the shared NULL_SPAN -- no generator, no tags dict
    assert not trace.ENABLED
    t0 = time.perf_counter()
    for _ in range(n):
        with (trace.current().span("prep.launch", sigs=1, lanes=1)
              if trace.ENABLED else trace.NULL_SPAN) as sid:
            if sid:
                raise AssertionError
    guard_s = time.perf_counter() - t0
    assert guard_s / n < 2e-6, f"{guard_s / n * 1e9:.0f} ns/with-guard"


def test_disabled_path_at_the_decision_sites_touches_no_tracer(monkeypatch):
    """ISSUE 23: with tracing off, a whole commit decision -- entry point,
    verify service, ops prep, host route and forced device launch --
    allocates no span and takes no ring lock: every Tracer entry point of
    the DEFAULT tracer is booby-trapped, and the decision id stays 0. Nor
    does the entry point read a clock (ISSUE 24: one loop serves both
    paths, and only the traced one times its sign bytes)."""
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto import verify_service
    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.types import validator_set as vset
    from tests.test_perf_gate import CHAIN_ID, _commit

    assert not trace.ENABLED

    def boom(*_a, **_kw):
        raise AssertionError("a disabled site reached the tracer or a clock")

    class NoLock:
        def __enter__(self):
            boom()

        def __exit__(self, *exc):
            return False

    class NoClock:
        def __getattr__(self, name):
            boom()

    for name in ("span", "mark", "record", "annotate", "_append", "_stacks"):
        monkeypatch.setattr(trace.DEFAULT, name, boom)
    monkeypatch.setattr(trace.DEFAULT, "_mtx", NoLock())
    # ISSUE 24: the entry point's only clock readings are the two around
    # sign_bytes_many, on the traced path
    monkeypatch.setattr(vset, "time", NoClock())
    monkeypatch.setenv("TMTPU_VERIFY_SERVICE", "1")
    # the launch site is the host's; the kernel itself is the slow tier's
    monkeypatch.setattr(edb, "_jnp_kernel", lambda tab, **kw: kw["valid"])
    verify_service.reset()
    try:
        vals, commit = _commit(24)
        for entry in (vals.verify_commit_async, vals.verify_commit_light_async):
            pcv = entry(CHAIN_ID, commit.block_id, commit.height, commit)
            assert isinstance(pcv.pending._children[0], cbatch.ServicePending)
            assert pcv._decision == 0 and pcv._tracer is None
            assert pcv.pending._children[0]._req.decision == 0
            pcv.resolve()
        # the ops layer's own sites, host route and forced device launch
        items = [(vals.validators[i].pub_key.data,
                  commit.vote_sign_bytes(CHAIN_ID, i),
                  commit.signatures[i].signature) for i in range(24)]
        assert edb.verify_batch(items).all()
        assert edb.verify_batch(items, force_device=True).all()
    finally:
        verify_service.reset()


@pytest.mark.parametrize("entry, mode, nil, sigs, spliced", [
    ("verify_commit_async", "full", (), 24, 24),
    ("verify_commit_light_async", "light", (), 17, 17),   # stops at +2/3
    ("verify_commit_async", "full", (1, 5, 20), 24, 21),  # nil votes fall back
    ("verify_commit_light_async", "light", (1, 5, 20), 17, 17),  # skips them
])
def test_commit_assemble_tags_its_sign_bytes_once_a_decision(
        tracer, monkeypatch, entry, mode, nil, sigs, spliced):
    """ISSUE 24: commit.assemble carries `sigs`, `spliced` (how many sign
    bytes the per-commit splice produced; the rest took the per-index path)
    and `sign_bytes_s` from exactly two clock readings a decision, whatever
    the number of signatures."""
    from tendermint_tpu.types import validator_set as vset
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_NIL
    from tests.test_perf_gate import CHAIN_ID, _commit

    vals, commit = _commit(24)
    for i in nil:  # the verdict is not under test, so the flag alone moves
        commit.signatures[i].block_id_flag = BLOCK_ID_FLAG_NIL

    class CountingClock:
        readings = 0

        def perf_counter(self):
            CountingClock.readings += 1
            return time.perf_counter()

    monkeypatch.setattr(vset, "time", CountingClock())
    with tracer.activate():
        pcv = getattr(vals, entry)(CHAIN_ID, commit.block_id, commit.height,
                                   commit)
    try:
        pcv.resolve()
    except vset.ValidatorSetError:
        assert nil  # a re-flagged vote's signature covers other bytes
    assert CountingClock.readings == 2
    root, = [s for s in tracer.dump() if s.name == "commit.assemble"]
    assert root.tags["mode"] == mode
    assert (root.tags["sigs"], root.tags["spliced"]) == (sigs, spliced)
    assert 0.0 < root.tags["sign_bytes_s"] < root.duration_s


def test_enabled_refcount_maintains_module_guard():
    base = trace.ENABLED
    a = trace.Tracer("ra")
    b = trace.Tracer("rb")
    a.enable()
    b.enable()
    assert trace.ENABLED
    a.disable()
    assert trace.ENABLED  # b still on
    a.disable()  # idempotent: must not underflow the refcount
    assert trace.ENABLED
    b.disable()
    assert trace.ENABLED == base


# ---------------------------------------------------------------------------
# 3. timeline / last_phase / metrics mirror
# ---------------------------------------------------------------------------


def test_timeline_lifecycle_census_and_causal_order(tracer):
    for name in trace.LIFECYCLE:
        tracer.mark(name, height=7, round=0)
    tracer.mark("consensus.proposal", height=8)  # other height: filtered
    tl = tracer.timeline(7)
    assert tl["lifecycle_complete"] and tl["causal_ok"]
    assert all(n == 1 for n in tl["lifecycle"].values())
    assert all(s["tags"]["height"] == 7 for s in tl["spans"])

    # out-of-order lifecycle (commit observed before proposal) is flagged
    t2 = trace.Tracer("ooo", enabled=True)
    try:
        t2.mark("consensus.commit", height=3)
        t2.mark("consensus.proposal", height=3)
        tl2 = t2.timeline(3)
        assert not tl2["causal_ok"] and not tl2["lifecycle_complete"]
    finally:
        t2.disable()


def test_timeline_phase_aggregation(tracer):
    with tracer.span("consensus.vote_drain", height=4):
        tracer.record("verify.queue", 0.25)
        tracer.record("verify.queue", 0.25)
    ph = tracer.timeline(4)["phases"]
    assert ph["verify.queue"]["count"] == 2
    assert ph["verify.queue"]["total_s"] == pytest.approx(0.5)


def test_last_phase_names_most_recent_completion(tracer):
    assert tracer.last_phase() is None
    tracer.mark("consensus.precommit", height=12, round=1)
    lp = tracer.last_phase()
    assert lp["name"] == "consensus.precommit"
    assert lp["height"] == 12 and lp["round"] == 1
    assert lp["age_s"] >= 0.0


def test_metrics_mirror_phase_and_step_histograms(tracer):
    from tendermint_tpu.utils import metrics as tmmetrics

    m = tmmetrics.NodeMetrics()
    text = m.registry.expose()
    # pre-seeded: every mirrored phase scrapes explicit zeros, with the
    # full histogram exposition (satellite 2)
    for phase in trace.MIRRORED_SPANS:
        assert (f'tendermint_trace_phase_seconds_count{{phase="{phase}"}} 0'
                in text), phase
    assert ('tendermint_trace_phase_seconds_bucket{phase="verify.readback"'
            ',le="+Inf"} 0') in text
    assert ('tendermint_trace_phase_seconds_sum{phase="verify.readback"} 0.0'
            in text)
    assert ('tendermint_consensus_step_duration_seconds_count'
            '{step="RoundStepPropose"} 0') in text
    tmmetrics.GLOBAL_NODE_METRICS = m
    try:
        tracer.record("verify.readback", 0.02, height=1)
        tracer.record("consensus.step", 0.01, step="RoundStepPropose")
        text = m.registry.expose()
        assert ('tendermint_trace_phase_seconds_count'
                '{phase="verify.readback"} 1') in text
        assert ('tendermint_consensus_step_duration_seconds_count'
                '{step="RoundStepPropose"} 1') in text
    finally:
        tmmetrics.GLOBAL_NODE_METRICS = None


def test_pending_verify_spans_via_production_dispatch(tracer):
    """The crypto-layer phases fire through the real dispatch()/resolve()
    contract and inherit the drain height captured at dispatch time."""
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto import ed25519

    priv = ed25519.gen_priv_key(b"\x77" * 32)
    pub = priv.pub_key()
    items = [(pub, b"m%d" % i, ed25519.sign(priv.data, b"m%d" % i))
             for i in range(64)]
    with tracer.activate():
        with tracer.span("consensus.vote_drain", height=21, votes=64):
            v = crypto_batch.create_batch_verifier("ed25519")
            for p, msg, sig in items:
                v.add(p, msg, sig)
            pending = v.dispatch()
        ok, bitmap = pending.resolve()
    assert ok and all(bitmap)
    agg = tracer.summarize()
    assert agg.get("verify.host_prep", {}).get("count") == 1
    # queue wait recorded between dispatch and resolve, on the height
    # captured at dispatch
    queue_spans = [s for s in tracer.dump() if s.name == "verify.queue"]
    assert queue_spans and queue_spans[0].tags.get("height") == 21


# ---------------------------------------------------------------------------
# 4. 3-node mesh smoke: the committed-height timeline end to end
# ---------------------------------------------------------------------------


def _rpc(base: str, method: str, params: dict):
    body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                       "params": params}).encode()
    with urllib.request.urlopen(urllib.request.Request(
            base, data=body, headers={"Content-Type": "application/json"}),
            timeout=10) as r:
        doc = json.loads(r.read())
    assert "error" not in doc, doc
    return doc["result"]


def test_three_node_mesh_timeline_smoke(tmp_path):
    """Satellite 4 + acceptance: a committed height's timeline contains
    every lifecycle phase exactly once, in causal order, on every node —
    and the unsafe_timeline/unsafe_trace RPC routes serve it."""
    from tendermint_tpu.e2e.fabric import Cluster

    cluster = Cluster(str(tmp_path), 3, topology="full", rpc_node=0,
                      trace=True)
    cluster.start()
    try:
        assert cluster.wait_min_height(4, timeout=120), cluster.heights()
        floor = cluster.min_height()
        # scan recent fully-committed heights (newest first: ring-eviction
        # safe) for one every node saw in a single round
        found = None
        for h in range(floor - 1, 1, -1):
            tls = [cluster.nodes[i].node.tracer.timeline(h) for i in (0, 1, 2)]
            if all(tl["lifecycle_complete"] and tl["causal_ok"]
                   and all(n == 1 for n in tl["lifecycle"].values())
                   for tl in tls):
                found = h
                break
        assert found is not None, {
            i: cluster.nodes[i].node.tracer.timeline(floor - 1)["lifecycle"]
            for i in (0, 1, 2)}

        # the RPC surface: unsafe_timeline serves the same structure
        rpc = cluster.nodes[0].node.rpc_server
        base = "http://" + rpc.laddr.split("://", 1)[1]
        tl = _rpc(base, "unsafe_timeline", {"height": found})
        assert tl["height"] == found and tl["lifecycle_complete"]
        assert tl["causal_ok"] and tl["spans"]
        # unsafe_trace: state + aggregation, and live disable/enable
        view = _rpc(base, "unsafe_trace", {})
        assert view["enabled"] and view["spans"] > 0
        assert "consensus.step" in view["summary"]
        view = _rpc(base, "unsafe_trace", {"enable": False})
        assert not view["enabled"]
        view = _rpc(base, "unsafe_trace", {"enable": True})
        assert view["enabled"]
    finally:
        cluster.stop()
