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
        # ISSUE 35: every span names its thread; the height has its census
        # of threads and its receive mark, once each
        assert all(s["thread"] and "cpu_s" in s for s in tl["spans"])
        assert tl["phases"]["consensus.thread_cpu"]["count"] == 1
        assert tl["phases"]["consensus.recv"]["count"] == 1
        census = next(s["tags"] for s in tl["spans"]
                      if s["name"] == "consensus.thread_cpu")
        assert census["threads"]["cs-receive"] > 0 and census["wall_s"] > 0
        # unsafe_trace: state + aggregation, and live disable/enable
        view = _rpc(base, "unsafe_trace", {})
        assert view["enabled"] and view["spans"] > 0
        assert "consensus.step" in view["summary"]
        view = _rpc(base, "unsafe_trace", {"enable": False})
        assert not view["enabled"]
        # the table of threads is served with tracing off too
        assert view["threads"]["threads"]["cs-receive"] > 0
        assert view["threads"]["process_s"] > 0
        view = _rpc(base, "unsafe_trace", {"enable": True})
        assert view["enabled"]
    finally:
        cluster.stop()


# ---------------------------------------------------------------------------
# 5. ISSUE 35: which thread ran a span and how much CPU that thread got
# ---------------------------------------------------------------------------


def _spin(seconds: float) -> None:
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        pass


def test_a_sleeping_span_has_no_cpu_and_a_spinning_one_has_its_duration(tracer):
    with tracer.span("consensus.flush_wait"):
        time.sleep(0.1)
    with tracer.span("consensus.vote_apply"):
        _spin(0.1)
    slept, spun = tracer.dump()
    assert slept.duration_s >= 0.1 and slept.cpu_s < 0.02
    assert 0.05 < spun.cpu_s <= spun.duration_s
    for s in (slept, spun):
        assert s.as_dict()["cpu_s"] == s.cpu_s
        assert s.as_dict()["thread"] == threading.current_thread().name


def test_a_span_names_the_thread_that_closed_it(tracer):
    def work():
        with tracer.span("verify.host_prep"):
            tracer.mark("consensus.commit")
        tracer.record("verify.queue", 0.01)

    threads = [threading.Thread(target=work, name=f"writer-{k}")
               for k in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=5)
        assert not th.is_alive()
    work()
    by_thread: dict = {}
    for s in tracer.dump():
        by_thread.setdefault(s.thread, []).append(s.name)
    assert by_thread == {
        name: ["consensus.commit", "verify.host_prep", "verify.queue"]
        for name in ("writer-0", "writer-1", threading.current_thread().name)}


def test_record_stores_the_cpu_it_is_given_and_a_mark_has_none(tracer):
    tracer.record("verify.queue", 0.25)
    tracer.record("consensus.vote_serial", 0.25, cpu_s=0.125, why="late")
    tracer.mark("consensus.commit")
    waited, serial, mark = tracer.dump()
    assert waited.cpu_s is None and waited.as_dict()["cpu_s"] is None
    assert serial.cpu_s == 0.125
    assert mark.cpu_s is None and mark.thread == threading.current_thread().name


def test_summarize_and_timeline_sum_cpu_beside_wall(tracer):
    tracer.record("consensus.vote_serial", 0.5, cpu_s=0.125, height=3)
    tracer.record("consensus.vote_serial", 0.25, cpu_s=0.0625, height=3)
    tracer.record("verify.queue", 1.0, height=3)       # a wait: no CPU
    with tracer.span("consensus.vote_apply", height=3):
        _spin(0.02)
    agg = tracer.summarize()
    assert agg["consensus.vote_serial"] == {
        "count": 2, "total_s": 0.75, "cpu_s": 0.1875, "max_s": 0.5}
    assert agg["verify.queue"]["cpu_s"] == 0.0
    assert 0.0 < agg["consensus.vote_apply"]["cpu_s"] <= \
        agg["consensus.vote_apply"]["total_s"]
    phases = tracer.timeline(3)["phases"]
    assert phases["consensus.vote_serial"] == {
        "count": 2, "total_s": 0.75, "cpu_s": 0.1875}
    assert phases["consensus.vote_apply"]["cpu_s"] == \
        agg["consensus.vote_apply"]["cpu_s"]
    assert all("thread" in s and "cpu_s" in s
               for s in tracer.timeline(3)["spans"])


def test_the_startup_ring_carries_both_fields():
    ring = trace.Tracer(name="startup", cap=8, cold=True)
    with ring.span("startup.table_build", keys=1):
        _spin(0.02)
    ring.record("startup.jit_trace", 0.5, cpu_s=0.4, fun="f")
    build, traced = ring.dump()
    assert 0.0 < build.cpu_s <= build.duration_s
    assert traced.cpu_s == 0.4
    assert {s.thread for s in (build, traced)} == {
        threading.current_thread().name}
    assert ring.summarize()["startup.jit_trace"]["cpu_s"] == 0.4


needs_thread_clocks = pytest.mark.skipif(
    not hasattr(time, "pthread_getcpuclockid"),
    reason="no per-thread CPU clock on this platform")


@needs_thread_clocks
def test_the_census_counts_the_born_in_full_and_the_dead_as_lost():
    stop = threading.Event()
    old = threading.Thread(target=stop.wait, name="census-old")
    old.start()
    census = trace.ThreadCensus()
    assert census.read() is None            # the first reading: a baseline
    spun = threading.Event()

    def burn(cpu_s):            # until this thread has had that much CPU
        c0 = time.thread_time()
        while time.thread_time() - c0 < cpu_s:
            pass

    born = threading.Thread(
        target=lambda: (burn(0.05), spun.set(), stop.wait()),
        name="census-born")
    born.start()
    burn(0.05)
    assert spun.wait(timeout=60)
    got = census.read()
    try:
        assert got["threads"]["census-born"] >= 0.05      # its whole reading
        assert got["threads"]["census-old"] < 0.02
        assert got["threads"][threading.current_thread().name] >= 0.05
        # the threads Python sees and the rest are the process
        assert sum(got["threads"].values()) + got["rest_s"] == \
            pytest.approx(got["process_s"], abs=1e-9)
        assert abs(got["rest_s"]) < 0.05 + 0.2 * got["process_s"]
        assert got["wall_s"] >= 0.05
    finally:
        stop.set()
        for th in (old, born):
            th.join(timeout=5)
            assert not th.is_alive()
    got = census.read()
    assert got["lost"] >= 2     # these two (and whatever else the process lost)
    assert not {"census-old", "census-born"} & set(got["threads"])
    # the table the unsafe_trace route serves: cumulative, by name
    table = trace.thread_cpu_table()
    assert table["threads"][threading.current_thread().name] >= 0.05
    assert table["process_s"] >= table["threads"][
        threading.current_thread().name]


def test_without_thread_clocks_the_census_is_absent(monkeypatch):
    monkeypatch.setattr(trace, "_THREAD_CLOCKS", False)
    assert trace.thread_cpu_times() is None
    assert trace.thread_cpu_table() is None
    assert trace.ThreadCensus().read() is None


def test_disabled_tracer_reads_no_cpu_clock_and_takes_no_census(monkeypatch):
    """ISSUE 35: the off path stays what it was. No span, record, mark or
    guarded site reads ``time.thread_time``; the drain and the reactor's
    ``receive`` take no census and write no mark."""
    from tendermint_tpu.consensus import reactor as cs_reactor

    def boom(*_a, **_kw):
        raise AssertionError("a disabled site read a CPU clock")

    assert not trace.ENABLED
    monkeypatch.setattr(time, "thread_time", boom)
    for name in ("thread_cpu_times", "thread_cpu_table"):
        monkeypatch.setattr(trace, name, boom)
    monkeypatch.setattr(trace.ThreadCensus, "read", boom)
    t = trace.Tracer("off")
    with t.span("consensus.vote_drain", height=1) as sid:
        assert sid == 0
    t.record("consensus.vote_serial", 0.1, cpu_s=0.1)
    t.mark("consensus.thread_cpu")
    t.annotate(votes=1)
    with (trace.current().span("prep.launch", sigs=1, lanes=1)
          if trace.ENABLED else trace.NULL_SPAN) as sid:
        assert sid == 0
    assert t.dump() == []
    # the live path: a drain and a single vote through the receive loop, and
    # a message through the reactor, with every tracer off
    cs, wal_records, counted = _drain_through_the_receive_loop(None)
    assert len(counted) == 11 and wal_records
    r = cs_reactor.ConsensusReactor(cs)
    r._receive = lambda *a: None
    r.receive(0x22, object(), b"\x00")
    r._mark_recv(cs.rs)
    assert r.recv_stats == {} and not r._recv_callers


def _drain_through_the_receive_loop(tracer, tmp_path=None):
    """24 validators, one height: a drain of 14 deliveries (ten good votes,
    a copy, a flipped signature, a vote of the next height, an index out of
    range), a non-vote that ends it, then one vote alone -> (machine, the
    WAL's records, the votes it counted). ``tracer`` None leaves the
    machine's default (disabled) tracer."""
    import tempfile

    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.consensus import cstypes
    from tendermint_tpu.consensus.state_machine import (
        ConsensusState,
        MsgInfo,
        VoteMessage,
    )
    from tendermint_tpu.consensus.wal import WAL
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.ttime import Time
    from tendermint_tpu.types.vote import PREVOTE_TYPE
    from tests.test_vote_batching import CHAIN_ID, _net, _signed_vote

    privs, _ = _net(24)
    state = make_genesis_state(GenesisDoc(
        chain_id=CHAIN_ID, genesis_time=Time(1700001000, 0),
        validators=[GenesisValidator(b"", p.pub_key(), 10) for p in privs]))
    wal_dir = tempfile.mkdtemp(prefix="trace-wal-", dir=tmp_path)
    cs = ConsensusState(test_config().consensus, state, None, None,
                        wal=WAL(wal_dir))
    if tracer is not None:
        cs.tracer = tracer
    vals = cs.rs.votes.val_set
    bid = BlockID(hash=b"\x77" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x88" * 32))
    votes = [_signed_vote(p, vals, PREVOTE_TYPE, bid) for p in privs[:11]]
    flipped = _signed_vote(privs[11], vals, PREVOTE_TYPE, bid)
    flipped.signature = bytes([flipped.signature[0] ^ 1]) + flipped.signature[1:]
    early = _signed_vote(privs[12], vals, PREVOTE_TYPE, bid)
    early.height = 2
    stray = _signed_vote(privs[13], vals, PREVOTE_TYPE, bid)
    stray.validator_index = 99
    counted: list = []
    cs.on_vote.append(lambda v: counted.append(
        (v.type, v.height, v.validator_index)))
    cs.rs.step = cstypes.STEP_PREVOTE
    drained, ended = threading.Event(), threading.Event()
    for v in votes[:10] + [votes[3], flipped, early, stray]:
        cs._msg_queue.put(MsgInfo(VoteMessage(v), "peerX"))
    cs._msg_queue.put(("__sync__", drained))    # ends the drain
    cs._msg_queue.put(MsgInfo(VoteMessage(votes[10]), "peerY"))
    cs._running = True
    loop = threading.Thread(target=cs._receive_routine, name="cs-receive")
    loop.start()
    try:
        assert drained.wait(timeout=30)
        # the vote alone is taken once the queue behind it is empty
        deadline = time.monotonic() + 30
        while len(counted) < 11 and time.monotonic() < deadline:
            time.sleep(0.01)
        cs._msg_queue.put(("__sync__", ended))
        assert ended.wait(timeout=30)
    finally:
        cs._running = False
        cs._msg_queue.put(None)
        loop.join(timeout=10)
        assert not loop.is_alive()
        cs.wal.close()
    records = [(tm.msg.peer_id, bytes(tm.msg.payload))
               for tm, _at in WAL(wal_dir).iter_messages()]
    return cs, records, counted


def test_the_merged_drain_paths_write_and_count_the_same_traced_or_not(
        tmp_path):
    """ISSUE 35 (D17): one path where PR 30 wrote two. With the tracer on
    and off the receive loop writes the same WAL records in the same order
    and counts the same votes; on, it names the phases with their CPU."""
    cs_off, wal_off, counted_off = _drain_through_the_receive_loop(
        trace.Tracer("off"), tmp_path)
    t = trace.Tracer("on", enabled=True)
    try:
        cs_on, wal_on, counted_on = _drain_through_the_receive_loop(t, tmp_path)
    finally:
        t.disable()
    assert wal_on == wal_off
    assert [peer for peer, _ in wal_on if peer] == ["peerX"] * 14 + ["peerY"]
    assert counted_on == counted_off and len(counted_on) == 11
    for cs in (cs_on, cs_off):
        assert sum(cs.rs.votes.prevotes(0).bit_array()) == 11
    assert cs_off.tracer.dump() == []
    spans = {s.name: s for s in t.dump()}
    by_why = {s.tags["why"]: s for s in t.dump()
              if s.name == "consensus.vote_serial"}
    assert spans["consensus.wal_write"].tags["msgs"] == 14
    assert spans["consensus.wal_write"].tags["bytes"] > 0
    assert spans["consensus.wal_write"].tags["writes"] == 1   # one pass
    assert spans["consensus.vote_drain"].tags["votes"] == 14
    apply_ = spans["consensus.vote_apply"]
    assert (apply_.tags["votes"], apply_.tags["added"]) == (14, 10)
    assert apply_.tags["duplicates"] >= 1 and apply_.tags["invalid"] == 1
    assert set(by_why) == {"early", "precheck", "single"}
    names = {s.name for s in t.dump()}
    assert {"consensus.wal_write", "consensus.vote_drain", "consensus.vote_apply",
            "consensus.vote_serial"} <= names
    for s in t.dump():
        assert s.thread == "cs-receive"
        if s.name in ("consensus.step", "verify.queue", "verify.wake"):
            assert s.cpu_s is None          # waits between two events
        elif s.name.startswith("consensus.") and s.duration_s:
            # span()s, and vote_serial's record() of accumulated thread_time()
            assert 0.0 <= s.cpu_s <= s.duration_s + 1e-3, s


def test_a_drain_goes_frame_by_frame_while_a_fault_rule_is_armed(tracer,
                                                                 tmp_path):
    """ISSUE 41: ``wal.write``'s hit index counts frames, so with any rule
    armed a drain hands the file a frame at a time (``writes`` = ``msgs``)
    and writes the records the one-pass path writes."""
    from tendermint_tpu.utils import faults

    _cs, wal_one_pass, _ = _drain_through_the_receive_loop(
        trace.Tracer("off"), tmp_path)
    faults.configure(["wal.write:torn@1000"])   # armed, never reached
    try:
        _cs, wal_armed, counted = _drain_through_the_receive_loop(
            tracer, tmp_path)
        hits, fired = faults.snapshot()
    finally:
        faults.clear()
    write = next(s for s in tracer.dump() if s.name == "consensus.wal_write")
    assert write.tags["msgs"] == write.tags["writes"] == 14
    assert hits["wal.write"] >= 15 and not fired    # the drain's 14, then one
    assert wal_armed == wal_one_pass and len(counted) == 11


def test_receive_reads_no_cpu_clock_and_the_mark_has_its_callers_cpu(tracer):
    """The CPU clock is a system call (10 us and more on the benchmark's
    host): ``receive`` notes who called and reads no CPU clock; once a height
    the mark reads the callers' clocks from outside."""
    import types

    from tendermint_tpu.consensus import reactor as cs_reactor

    cs, _wal, _counted = _drain_through_the_receive_loop(tracer)
    r = cs_reactor.ConsensusReactor(cs)
    r._receive = lambda *a: _spin(0.0005)
    r._mark_recv(types.SimpleNamespace(height=7))      # the baseline
    real = time.thread_time

    def deliver():
        for _ in range(40):
            r.receive(0x22, object(), b"\x00" * 100)

    try:
        time.thread_time = lambda: (_ for _ in ()).throw(AssertionError(
            "receive read the CPU clock"))
        other = threading.Thread(target=deliver, name="deliverer-2")
        other.start()
        deliver()
        other.join(timeout=30)
        assert not other.is_alive()
    finally:
        time.thread_time = real
    assert r.recv_stats[0x22][0] == 80 and r.recv_stats[0x22][2] == 8000
    tracer.clear()
    r._mark_recv(types.SimpleNamespace(height=8))
    r._mark_recv(types.SimpleNamespace(height=8))       # once a height
    (mark,) = [s for s in tracer.dump() if s.name == "consensus.recv"]
    tags = mark.tags
    assert (tags["height"], tags["msgs"], tags["bytes"]) == (7, 80, 8000)
    # a thread that has exited by the time of the mark is named, its CPU lost
    assert tags["threads"] == sorted(["deliverer-2",
                                      threading.current_thread().name])
    assert tags["seconds"] == pytest.approx(r.recv_stats[0x22][1])
    if trace.thread_cpu_times() is not None:
        # this thread's 40 messages of 0.5 ms of spinning, and a little more
        assert 0.015 <= tags["cpu_s"] < 1.0
    else:
        assert tags["cpu_s"] is None
    assert len(r.recv_stats[0x22]) == 3                 # the shape is fixed
