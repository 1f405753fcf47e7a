"""ISSUE 11: the continuous-batching verify service (crypto/verify_service).

Coalescing CORRECTNESS is the whole game: N threads dispatching
overlapping ed25519/sr25519/mixed batches concurrently must get bitmaps
bit-identical to serial dispatch, with tampered lanes attributed to the
right caller; a breaker trip mid-coalesce must fall back to host without
losing or double-resolving a single waiter; and the PendingVerify
semantics (has_device_output / resolve idempotence / prefetch) must be
unchanged so every existing caller rides the service transparently.

A generous TMTPU_VERIFY_WINDOW_US makes the concurrent tests'
coalescing deterministic: all threads submit well inside one window, so
the executor provably shares one launch (asserted via service stats)."""

import threading

import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519, sr25519, verify_service

CHAIN = b"svc-test"


@pytest.fixture(autouse=True)
def _fresh_service(monkeypatch):
    # force-all mode: on this host the C verifier absorbs sub-crossover
    # batches with no floor, so adaptive routing would keep these small
    # test batches off the service; =1 pins them on (exactly what the
    # concurrent bench and graft stage do)
    monkeypatch.setenv("TMTPU_VERIFY_SERVICE", "1")
    monkeypatch.setenv("TMTPU_VERIFY_WINDOW_US", "50000")
    verify_service.reset()
    yield
    verify_service.reset()


def _tamper(sig: bytes) -> bytes:
    return sig[:-1] + bytes([sig[-1] ^ 1])


def _ed_items(n, seed, tampered=()):
    out = []
    for i in range(n):
        priv = ed25519.gen_priv_key(bytes([seed]) * 16 + i.to_bytes(16, "big"))
        msg = CHAIN + b"-ed-%d-%d" % (seed, i)
        sig = ed25519.sign(priv.data, msg)
        out.append((priv.pub_key(), msg, _tamper(sig) if i in tampered else sig))
    return out


def _sr_items(n, seed, tampered=()):
    out = []
    for i in range(n):
        priv = sr25519.gen_priv_key(bytes([seed]) * 16 + i.to_bytes(16, "big"))
        msg = CHAIN + b"-sr-%d-%d" % (seed, i)
        sig = priv.sign(msg)
        out.append((priv.pub_key(), msg, _tamper(sig) if i in tampered else sig))
    return out


def _dispatch(key_type, items):
    v = cbatch.create_batch_verifier(key_type)
    for pk, m, s in items:
        v.add(pk, m, s)
    return v.dispatch()


def _run(key_type, items):
    return _dispatch(key_type, items).resolve()


def _serial_truth(items):
    return [pk.verify_signature(m, s) for (pk, m, s) in items]


def _concurrent(workloads):
    """Run each (key_type, items) on its own thread; all submissions land
    inside one coalescing window. Returns results parallel to workloads."""
    results = [None] * len(workloads)
    errors = []

    def worker(k, key_type, items):
        try:
            results[k] = _run(key_type, items)
        except Exception as e:  # noqa: BLE001 - surfaced in the test body
            errors.append((k, e))

    threads = [threading.Thread(target=worker, args=(k, kt, its))
               for k, (kt, its) in enumerate(workloads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return results


def test_concurrent_overlapping_batches_bit_identical_to_serial():
    """N threads, overlapping ed/sr/mixed batches (shared keys between the
    two ed callers), one coalescing window: every caller's (all_ok, bitmap)
    equals both serial dispatch (service off) and the scalar ground truth,
    and tampered lanes land on the right caller at the right index."""
    ed_a = _ed_items(40, seed=1, tampered={5})
    # overlaps ed_a's keys: same seed, shifted tamper — exercises the
    # reuse of resident key rows inside one coalesced generation
    ed_b = _ed_items(40, seed=1, tampered={17})
    sr_a = _sr_items(9, seed=2, tampered={2})
    mixed = ed_a[:6] + sr_a[:3] + ed_a[6:12]
    workloads = [("ed25519", ed_a), ("ed25519", ed_b),
                 ("sr25519", sr_a), (None, mixed)]

    got = _concurrent(workloads)
    svc = verify_service.get()
    assert svc.requests >= 4
    assert svc.max_coalesced >= 2, (
        "concurrent dispatches inside one window did not coalesce: "
        f"launches={svc.launches} requests={svc.requests}")

    import os
    os.environ["TMTPU_VERIFY_SERVICE"] = "0"
    try:
        serial = [_run(kt, its) for (kt, its) in workloads]
    finally:
        del os.environ["TMTPU_VERIFY_SERVICE"]

    for k, (kt, its) in enumerate(workloads):
        truth = _serial_truth(its)
        assert got[k] == serial[k], f"caller {k} ({kt}): service != serial"
        assert got[k] == (all(truth), truth), f"caller {k}: != ground truth"
    # attribution spot checks: each tampered lane fails for ITS caller only
    assert got[0][1][5] is False and got[1][1][5] is True
    assert got[1][1][17] is False and got[0][1][17] is True
    assert got[2][1][2] is False


def test_breaker_trip_mid_coalesce_resolves_every_waiter_once(monkeypatch):
    """TMTPU_FAULTS device failure while several callers share one
    generation: the injected raise at the coalesced ops dispatch opens the
    circuit, the generation degrades to the host fallback, and EVERY
    waiter resolves exactly once with the correct bitmap."""
    import os

    from tendermint_tpu.ops import ed25519_batch
    from tendermint_tpu.utils import faults

    monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "0")  # pin the device route
    monkeypatch.setenv("TM_TPU_BREAKER_COOLDOWN_S", "3600")  # no re-probe
    monkeypatch.setenv("TMTPU_FAULTS", "ops.ed25519.device:raise")
    faults.install_from_env()
    workloads = [("ed25519", _ed_items(34, seed=k, tampered={k}))
                 for k in range(3)]
    try:
        got = _concurrent(workloads)
    finally:
        monkeypatch.setenv("TMTPU_FAULTS", "")
        faults.install_from_env()
        ed25519_batch.BREAKER.reset()
    assert ed25519_batch.BREAKER.failures >= 1, "the fault never fired"
    for k, (kt, its) in enumerate(workloads):
        truth = _serial_truth(its)
        assert got[k] == (all(truth), truth), f"caller {k} wrong after trip"
        assert got[k][1][k] is False
    assert os.environ.get("TMTPU_FAULTS") == ""


def test_executor_dispatch_crash_falls_back_without_losing_waiters(monkeypatch):
    """A failure that escapes even the breaker (ops.dispatch_batch itself
    raising, e.g. a prep bug) resolves every waiter through the scalar
    floor — the service must never deadlock a caller."""
    from tendermint_tpu.ops import ed25519_batch

    def boom(items, force_device=False):
        raise RuntimeError("injected dispatch crash")

    monkeypatch.setattr(ed25519_batch, "dispatch_batch", boom)
    workloads = [("ed25519", _ed_items(33, seed=7, tampered={1})),
                 ("ed25519", _ed_items(33, seed=8))]
    got = _concurrent(workloads)
    svc = verify_service.get()
    assert svc.fallbacks >= 1
    for k, (_, its) in enumerate(workloads):
        truth = _serial_truth(its)
        assert got[k] == (all(truth), truth)


def test_service_pending_semantics_and_prefetch():
    """ServicePending honors the PendingVerify contract: in-flight handles
    report has_device_output() (async callers stash them), resolve() is
    idempotent, and prefetch/resolve_all over service-backed handles just
    works."""
    pendings = [_dispatch("ed25519", _ed_items(33, seed=11)),
                _dispatch("ed25519", _ed_items(33, seed=12, tampered={3}))]
    assert all(isinstance(p, cbatch.ServicePending) for p in pendings)
    results = cbatch.resolve_all(pendings)
    assert results[0][0] is True
    assert results[1][0] is False and results[1][1][3] is False
    for p in pendings:
        assert not p.has_device_output()
        assert p.resolve() is p.resolve()  # cached, idempotent


def test_vote_drain_stash_engages_through_mixed_router(monkeypatch):
    """The consensus drain's overlap test-point: a mixed-registry dispatch
    whose sub-batches ride the service must report has_device_output()
    while the shared launch is in flight (the drain stashes and keeps
    draining), and resolve to the exact serial decision afterwards."""
    # a 2 s window (vs the fixture's 50 ms) makes the in-flight assertion
    # robust to CI scheduler stalls between dispatch and the check
    monkeypatch.setenv("TMTPU_VERIFY_WINDOW_US", "2000000")
    verify_service.reset()
    items = _ed_items(36, seed=21, tampered={9})
    p = _dispatch(None, items)
    # the coalescing window is still open: the launch cannot have completed
    assert p.has_device_output(), (
        "mixed handle hides the in-flight service launch — the vote drain "
        "would lose its dispatch/drain overlap")
    ok, bitmap = p.resolve()
    truth = _serial_truth(items)
    assert (ok, bitmap) == (all(truth), truth)


def test_service_off_restores_direct_dispatch(monkeypatch):
    monkeypatch.setenv("TMTPU_VERIFY_SERVICE", "0")
    p = _dispatch("ed25519", _ed_items(33, seed=31))
    assert not isinstance(p, cbatch.ServicePending)
    ok, bitmap = p.resolve()
    assert ok and all(bitmap)


def test_keyset_unique_set_lru_survives_interleaving(monkeypatch):
    """The device-resident comb table keyed per key: a novel interleaving of
    already-resident keys (the normal shape of a coalesced generation), a
    subset of them, or sixteen other signer sets in between must map to the
    rows the keys already have, not build tables again. (The name dates
    from the 16-entry LRU of whole key sets that this table replaced.)"""
    from tendermint_tpu.ops import ed25519_batch as edb

    builds = {"n": 0}
    orig = edb._build_comb_tables_tiled

    def counting(a_neg):
        builds["n"] += 1
        return orig(a_neg)

    monkeypatch.setattr(edb, "_build_comb_tables_tiled", counting)
    monkeypatch.setattr(edb, "_KS_CACHE", type(edb._KS_CACHE)())
    monkeypatch.setattr(edb, "_KS_UNIQ_CACHE", type(edb._KS_UNIQ_CACHE)())
    pubs = [it[0].bytes() for it in _ed_items(6, seed=41)]
    seq_a = [pubs[0], pubs[1], pubs[2], pubs[0]]
    seq_b = [pubs[2], pubs[0], pubs[1], pubs[2], pubs[1]]  # same SET, new order
    ks_a, idx_a, ok_a = edb.get_keyset(seq_a)
    ks_b, idx_b, ok_b = edb.get_keyset(seq_b)
    assert builds["n"] == 1, "novel interleaving rebuilt the comb tables"
    assert ks_a is ks_b
    assert ok_a.all() and ok_b.all()
    # the remap must still point every item at its own key's row
    row = {p: idx_a[i] for i, p in enumerate(seq_a)}
    for i, p in enumerate(seq_b):
        assert idx_b[i] == row[p], "interleaved key_idx maps to wrong row"
    # more signer sets than any whole-set cache held (16), each a subset of
    # the resident keys in an order of its own: none builds, none evicts
    for k in range(20):
        subset = [pubs[(k + j) % 3] for j in range(1 + k % 3)]
        _ks, idx, _ok = edb.get_keyset(subset)
        assert [row[p] for p in subset] == list(idx)
    # the exact sequence again (its memo entry long evicted): same rows
    ks_a2, idx_a2, _ = edb.get_keyset(seq_a)
    assert ks_a2 is ks_a and (idx_a2 == idx_a).all()
    assert builds["n"] == 1
    # a key the table has not met builds one tile, for itself alone
    _ks, idx_c, _ = edb.get_keyset([pubs[1], pubs[3]])
    assert builds["n"] == 2 and idx_c[0] == row[pubs[1]] and idx_c[1] == 3


# ---------------------------------------------------------------------------
# ISSUE 23: one causal trace per commit decision, across the executor thread
# ---------------------------------------------------------------------------


@pytest.fixture
def tracer():
    from tendermint_tpu.utils import trace

    t = trace.Tracer("svc-trace", cap=4096, enabled=True)
    yield t
    t.disable()


def _commit_of(n):
    from tests.test_perf_gate import CHAIN_ID, _commit

    vals, commit = _commit(n)
    return vals, commit, CHAIN_ID


def _tree(spans, did):
    """The spans of decision `did`, checked to be ONE tree under its root."""
    mine = [s for s in spans if s.tags.get("decision") == did]
    ids = {s.span_id for s in mine}
    roots = [s for s in mine if s.parent_id == 0]
    assert [r.name for r in roots] == ["commit.assemble"]
    assert roots[0].span_id == did
    for s in mine:
        assert s.parent_id in ids or s is roots[0], (s.name, s.parent_id)
    return mine


@pytest.mark.parametrize("entry, mode", [("verify_commit_async", "full"),
                                         ("verify_commit_light_async", "light")])
def test_one_decision_is_one_tree_across_the_service_thread(tracer, entry, mode):
    """verify_commit_async(...).resolve() through the service: every span
    carries the root's decision id, only the root has parent_id 0, the
    executor's verify.* spans are real spans with the ops layer's prep.*
    spans nested under them, and starts are where the work began."""
    import time

    vals, commit, chain_id = _commit_of(40)
    with tracer.activate():
        t_call = time.monotonic()
        pcv = getattr(vals, entry)(chain_id, commit.block_id, commit.height,
                                   commit)
        pcv.resolve()
        t_done = time.monotonic()
    spans = tracer.dump()
    assert pcv._decision and all(
        s.tags.get("decision") == pcv._decision for s in spans)
    mine = _tree(spans, pcv._decision)
    assert len(mine) == len(spans)
    by_name = {}
    for s in mine:
        by_name.setdefault(s.name, []).append(s)
    assert set(by_name) >= {"commit.assemble", "commit.wait", "commit.tally",
                            "verify.queue", "verify.host_prep",
                            "verify.readback", "verify.replay",
                            "prep.host_verify", "prep.scalars"}
    root = by_name["commit.assemble"][0]
    sigs = 40 if mode == "full" else 27            # light stops at +2/3
    assert root.tags["mode"] == mode and root.tags["sigs"] == sigs
    assert 0.0 < root.tags["sign_bytes_s"] < root.duration_s
    # no verify.* / prep.* span is a root, and the executor's are the
    # decision's children though they ran on another thread
    for s in mine:
        if s.name.startswith(("verify.", "prep.", "commit.w", "commit.t")):
            assert s.parent_id != 0, s.name
    prep = by_name["verify.host_prep"][0]
    assert prep.parent_id == root.span_id
    assert prep.tags["kind"] == "ed25519" and prep.tags["sigs"] == sigs
    host = by_name["prep.host_verify"][0]
    assert host.parent_id == prep.span_id and host.tags["route"] == "host_c"
    assert by_name["prep.scalars"][0].parent_id == host.span_id
    # the queue wait starts at the submit (inside the root span), not at
    # the moment the executor wrote it down; every span lies in the call
    q = by_name["verify.queue"][0]
    assert root.start <= q.start <= root.start + root.duration_s
    assert abs((q.start + q.duration_s) - prep.start) < 0.05
    rb, rp = by_name["verify.readback"][0], by_name["verify.replay"][0]
    assert rb.start + rb.duration_s <= rp.start + 1e-6
    for s in mine:
        assert t_call <= s.start and s.start + s.duration_s <= t_done + 1e-6
    for wake in by_name.get("verify.wake", []):
        assert wake.start >= rp.start + rp.duration_s - 1e-6


def test_interleaved_decisions_do_not_share_spans(tracer):
    """Two decisions in flight at once, resolved out of order: each span
    belongs to exactly one of the two trees."""
    import time

    vals, commit, chain_id = _commit_of(33)
    with tracer.activate():
        a = vals.verify_commit_async(chain_id, commit.block_id, commit.height,
                                     commit)
        deadline = time.monotonic() + 20
        while a.pending.has_device_output() and time.monotonic() < deadline:
            time.sleep(0.005)        # A's launch is over: B cannot join it
        b = vals.verify_commit_light_async(chain_id, commit.block_id,
                                           commit.height, commit)
        b.resolve()
        a.resolve()
    spans = tracer.dump()
    assert a._decision and b._decision and a._decision != b._decision
    tree_a, tree_b = _tree(spans, a._decision), _tree(spans, b._decision)
    assert len(tree_a) + len(tree_b) == len(spans)
    assert not {s.span_id for s in tree_a} & {s.span_id for s in tree_b}
    for tree in (tree_a, tree_b):
        names = [s.name for s in tree]
        for once in ("commit.assemble", "commit.wait", "commit.tally",
                     "verify.host_prep"):
            assert names.count(once) == 1, (once, names)
        assert not any("decisions" in s.tags for s in tree)


def test_a_coalesced_launch_names_every_decision_it_serves(tracer):
    """Two decisions submitted inside one window share ONE launch: its
    spans sit in the first decision's tree and carry decisions=[both]; the
    other tracer-less bookkeeping (queue, wake, wait, tally) stays per
    decision."""
    vals, commit, chain_id = _commit_of(33)
    with tracer.activate():
        a = vals.verify_commit_async(chain_id, commit.block_id, commit.height,
                                     commit)
        b = vals.verify_commit_async(chain_id, commit.block_id, commit.height,
                                     commit)
        a.resolve()
        b.resolve()
    assert verify_service.get().max_coalesced == 2
    spans = tracer.dump()
    both = sorted([a._decision, b._decision])
    shared = [s for s in spans if s.name in ("verify.host_prep",
                                             "verify.readback",
                                             "verify.replay")]
    assert [s.name for s in shared].count("verify.host_prep") == 1
    for s in shared:
        assert s.tags["decisions"] == both and s.tags["coalesced"] == 2
        assert s.tags["decision"] == a._decision
        assert s.parent_id == a._decision
    for pcv in (a, b):
        names = [s.name for s in spans if s.tags.get("decision") == pcv._decision]
        for once in ("commit.assemble", "verify.queue", "commit.wait",
                     "commit.tally"):
            assert names.count(once) == 1, (once, names)


def test_another_nodes_tracer_gets_the_recorded_copy(tracer):
    """Two nodes' requests in one launch: the first holds the real spans,
    the other a recorded copy with the work's own start -- and the first
    holds no duplicate."""
    from tendermint_tpu.utils import trace

    other = trace.Tracer("svc-trace-2", cap=1024, enabled=True)
    try:
        items = _ed_items(33, seed=51)
        with tracer.activate():
            pa = _dispatch("ed25519", items)
        with other.activate():
            pb = _dispatch("ed25519", items)
        assert pa.resolve()[0] and pb.resolve()[0]
        assert verify_service.get().max_coalesced == 2
        real = {s.name: s for s in tracer.dump()}
        copy = {s.name: s for s in other.dump()}
        for name in ("verify.host_prep", "verify.readback", "verify.replay"):
            assert [s.name for s in tracer.dump()].count(name) == 1
            assert abs(copy[name].start - real[name].start) < 0.01, name
            assert copy[name].tags["coalesced"] == 2
        assert "prep.host_verify" in real and "prep.host_verify" not in copy
    finally:
        other.disable()


def _launches(tracer):
    return [s for s in tracer.dump() if s.name == "prep.launch"]


def test_route_tag_names_the_route_taken_and_lanes_the_padded_size(
        tracer, monkeypatch, fake_tpu_host):
    """A host-routed and a forced-device batch, then a sharded one on a TPU
    host of four chips (faked on the CPU's virtual devices): `route` is the
    route dispatch_batch took, the finish carries it for
    batch_verify_seconds, and sum(lanes) is the padded size actually
    launched."""
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.ops import ed25519_pallas as edp
    from tendermint_tpu.utils import metrics as tmmetrics

    raw = [(pk.bytes(), m, s) for pk, m, s in _ed_items(40, seed=61)]
    monkeypatch.setattr(tmmetrics, "GLOBAL_NODE_METRICS",
                        tmmetrics.NodeMetrics())
    # routing, tags and lane counts are the host's work: the kernel (slow
    # tier: test_ed25519_batch) and the chunk program (test_placed_chunks)
    # are stood in for by their `valid` argument, which keeps this test off
    # XLA:CPU's compiles of them
    monkeypatch.setattr(edb, "_jnp_kernel", lambda tab, **kw: kw["valid"])
    monkeypatch.setattr(edp, "_verify_chunk",
                        lambda tab, *cols: cols[-1].astype(jnp.int32))
    with tracer.activate():
        # host: below the crossover the C verifier answers, nothing launches
        dev, finish = edb.dispatch_batch(raw)
        assert dev is None and finish.route == "host_c"
        host = [s for s in tracer.dump() if s.name == "prep.host_verify"]
        assert [s.tags["route"] for s in host] == ["host_c"]
        assert host[0].tags["sigs"] == 40 and not _launches(tracer)
        tracer.clear()

        # forced device off a TPU: the jnp kernel in 256-lane tiles
        big = raw * 7                                     # 280 signatures
        dev, finish = edb.dispatch_batch(big, force_device=True)
        assert finish(cbatch._device_get(dev)).all() and finish.route == "jnp"
        got = _launches(tracer)
        assert [s.tags["route"] for s in got] == ["jnp", "jnp"]
        assert {s.tags["program"] for s in got} == {"jit__verify_kernel"}
        assert [s.tags["sigs"] for s in got] == [256, 24]
        assert sum(s.tags["lanes"] for s in got) == 2 * edb.JNP_TILE == 512
        keyset = [s for s in tracer.dump() if s.name == "prep.keyset"]
        assert keyset[0].tags["hit"] == "miss" and keyset[0].tags["keys"] == 280
        tracer.clear()

        # sharded: the same batch on four chips, five 64-lane chunks, the
        # fifth back on the first chip
        devices = fake_tpu_host(4, 64)
        monkeypatch.setattr(edb.KeySet, "gathered_lane",
                            lambda self, idx, device=None: None)
        assert edb.should_shard(len(big))
        p = _dispatch("ed25519", [(pk, m, s) for pk, m, s in
                                  _ed_items(40, seed=61)] * 7)
        assert p.resolve()[0]
        got = _launches(tracer)
        assert [s.tags["route"] for s in got] == ["sharded"] * 5
        assert {s.tags["program"] for s in got} == {"jit__verify_chunk"}
        assert [s.tags["sigs"] for s in got] == [64, 64, 64, 64, 24]
        assert [s.tags["lanes"] for s in got] == [64] * 5
        assert [s.tags["device"] for s in got] == [
            d.id for d in devices + devices[:1]]
        shard, = [s for s in tracer.dump()
                  if s.name == "verify.shard_dispatch"]
        assert {s.parent_id for s in got} == {shard.span_id}
        assert (shard.tags["kind"], shard.tags["n"], shard.tags["chunks"],
                shard.tags["devices"]) == ("ed25519", 280, 5, 4)
        assert [s.tags["hit"] for s in tracer.dump()
                if s.name == "prep.keyset"] == ["sequence"]
    # the dispatch was counted by its devices, and the service observed the
    # sharded launch under its route's label
    text = tmmetrics.GLOBAL_NODE_METRICS.registry.expose()
    assert 'tendermint_consensus_verify_sharded_total{devices="4"} 1' in text
    assert ('tendermint_consensus_batch_verify_seconds_count'
            '{route="sharded"} 1') in text
    for route in ("pallas", "jnp", "host_c", "host_scalar",
                  "breaker_fallback"):
        assert ('tendermint_consensus_batch_verify_seconds_count'
                f'{{route="{route}"}} 0') in text, route


def test_a_breaker_fallback_is_named_on_the_span_and_the_finish(
        tracer, monkeypatch):
    from tendermint_tpu.ops import ed25519_batch as edb
    from tendermint_tpu.utils import faults

    raw = [(pk.bytes(), m, s) for pk, m, s in _ed_items(9, seed=71)]
    monkeypatch.setattr(edb.BREAKER, "probe", None)
    edb.BREAKER.reset()
    faults.configure(["ops.ed25519.device:raise@1"], seed=3)
    try:
        with tracer.activate():
            dev, finish = edb.dispatch_batch(raw, force_device=True)
        assert dev is None and finish(None).all()
        assert finish.route == "breaker_fallback"
        host = [s for s in tracer.dump() if s.name == "prep.host_verify"]
        assert [s.tags["route"] for s in host] == ["breaker_fallback"]
    finally:
        faults.configure([], seed=0)
        edb.BREAKER.reset()
