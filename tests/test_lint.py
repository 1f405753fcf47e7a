"""tmlint (tools/tmlint) + the runtime lock-order witness
(utils/lockwitness.py): the static-analysis gate itself.

Three layers:

1. **The tier-1 gate**: the whole tree must lint clean — zero
   non-baselined findings from >= 8 active rules, in seconds (pure AST,
   no jax import). This is what turns every one-off review catch the
   rules encode into a permanently enforced invariant.
2. **Analyzer self-tests**: for each rule, fixture snippets that MUST
   trigger and MUST NOT trigger it; pragma + baseline handling; two runs
   produce byte-identical output.
3. **Witness unit tests**: the instrumented Lock/RLock records real
   acquisition-order cycles (two threads, opposite order), stays quiet on
   reentrant RLocks and Condition.wait, and bounds its own bookkeeping.
   (The two in-process mesh scenarios run under the witness in
   test_nemesis.py / test_overload.py.)
"""

import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.tmlint import checks  # noqa: E402,F401
from tools.tmlint import core  # noqa: E402
from tendermint_tpu.utils import lockwitness  # noqa: E402

pytestmark = pytest.mark.quick

# Knob-like tokens for fixtures are spliced so the repo-wide parity scan
# of THIS file's string constants never sees a fake knob.
_PFX = "TM_TPU_"
_CPFX = "TMTPU_"


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _project(tmp_path, files: dict, side: dict | None = None):
    for rel, content in {**files, **(side or {})}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)
    roots = sorted({rel.split("/")[0] for rel in files})
    return core.Project(str(tmp_path),
                        core.collect_files(str(tmp_path), roots))


def _run(tmp_path, files, rules, side=None):
    return core.run_rules(_project(tmp_path, files, side), rules)


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# 1. the tier-1 gate
# ---------------------------------------------------------------------------


def test_rule_registry_has_the_contracted_set():
    assert len(core.RULES) >= 9
    assert set(core.RULES) >= {
        "lock-held-call", "lock-order", "device-sync-choke-point",
        "thread-crash-surface", "daemon-or-joined", "metrics-discipline",
        "fault-site-registry", "trace-span-discipline", "config-knob-parity",
    }


def test_whole_tree_lints_clean_fast():
    """THE gate: zero non-baselined findings over the default scan set.
    A new finding means either fix the code or (rarely, with a review
    reason) pragma/baseline it — never ignore it."""
    t0 = time.monotonic()
    project = core.Project(
        REPO, core.collect_files(REPO, core.DEFAULT_PATHS))
    findings = core.run_rules(project)
    elapsed = time.monotonic() - t0
    new, baselined = core.split_baselined(findings, core.load_baseline())
    assert not new, (
        "tmlint found new violations (fix them, or pragma/baseline with "
        "a reason):\n" + "\n".join(f.render() for f in new))
    # the baseline is a grandfather list, not a dumping ground
    assert len(baselined) <= 10, (
        f"baseline has grown to {len(baselined)} entries — fix some")
    # pure-AST speed: the gate must stay ~free inside the tier-1 budget
    assert elapsed < 30, f"lint pass took {elapsed:.1f}s (budget blown)"


def test_cli_acceptance_command_exits_zero():
    """The documented invocation (docs/LINT.md):
    `python -m tools.tmlint tendermint_tpu tests` — subprocess-level so
    the CLI wiring itself is pinned, and timed (<~10 s acceptance)."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.tmlint", "tendermint_tpu", "tests"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, (
        f"lint CLI failed ({elapsed:.1f}s):\n{proc.stdout}\n{proc.stderr}")
    assert elapsed < 60, f"CLI lint took {elapsed:.1f}s"


def test_two_runs_identical_output():
    """Determinism: rules iterate sorted structures only, so two fresh
    scans of the same tree render byte-identically."""
    def one():
        project = core.Project(
            REPO, core.collect_files(REPO, ["tendermint_tpu"]))
        return [f.render() for f in core.run_rules(project)]

    assert one() == one()


# ---------------------------------------------------------------------------
# 2. per-rule fixtures: must-trigger / must-not-trigger
# ---------------------------------------------------------------------------


def test_lock_held_call_triggers_and_not(tmp_path):
    files = {"tendermint_tpu/m.py": (
        "import threading\n"
        "import time\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._mtx = threading.Lock()\n"
        "    def bad(self):\n"
        "        with self._mtx:\n"
        "            time.sleep(1)\n"
        "    def good(self):\n"
        "        with self._mtx:\n"
        "            x = 1\n"
        "        time.sleep(0)\n"
        "        return x\n"
        "    def cb_bad(self, on_ban):\n"
        "        with self._mtx:\n"
        "            on_ban('p')\n"
    )}
    fs = _run(tmp_path, files, ["lock-held-call"])
    lines = sorted(f.line for f in fs)
    assert lines == [8, 16], [f.render() for f in fs]


def test_lock_order_cycle_and_self_deadlock(tmp_path):
    files = {"tendermint_tpu/m.py": (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._amtx = threading.Lock()\n"
        "    def one(self, b):\n"
        "        with self._amtx:\n"
        "            b.btake()\n"
        "    def atake(self):\n"
        "        with self._amtx:\n"
        "            pass\n"
        "    def re(self):\n"
        "        with self._amtx:\n"
        "            self.atake()\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._bmtx = threading.Lock()\n"
        "    def btake(self):\n"
        "        with self._bmtx:\n"
        "            pass\n"
        "    def two(self, a):\n"
        "        with self._bmtx:\n"
        "            a.atake()\n"
    )}
    fs = _run(tmp_path, files, ["lock-order"])
    msgs = [f.message for f in fs]
    assert any("cycle" in m and "m.A._amtx" in m and "m.B._bmtx" in m
               for m in msgs), msgs
    assert any("non-reentrant" in m for m in msgs), msgs
    # RLock re-acquire via self-call is NOT a self-deadlock
    files2 = {"tendermint_tpu/m.py": files["tendermint_tpu/m.py"].replace(
        "threading.Lock()", "threading.RLock()")}
    fs2 = _run(tmp_path / "b", files2, ["lock-order"])
    assert not any("non-reentrant" in f.message for f in fs2)


def test_device_sync_choke_point_scoping(tmp_path):
    bad = {"tendermint_tpu/consensus/x.py":
           "import jax\n\ndef f(d):\n    return jax.device_get(d)\n"}
    ok_ops = {"tendermint_tpu/ops/k.py":
              "import jax\n\ndef f(d):\n    return jax.device_get(d)\n"}
    choke = {"tendermint_tpu/crypto/batch.py": (
        "import jax\n"
        "def _device_get(tree):\n"
        "    return jax.device_get(tree)\n"
        "def leak(tree):\n"
        "    return jax.device_get(tree)\n"
    )}
    assert _rules_of(_run(tmp_path / "a", bad, ["device-sync-choke-point"]))
    assert not _run(tmp_path / "b", ok_ops, ["device-sync-choke-point"])
    fs = _run(tmp_path / "c", choke, ["device-sync-choke-point"])
    assert [f.line for f in fs] == [5], [f.render() for f in fs]


def test_thread_crash_surface_and_daemon_rules(tmp_path):
    files = {"tendermint_tpu/m.py": (
        "import threading\n"
        "def naked():\n"
        "    x = 1\n"
        "def shielded():\n"
        "    try:\n"
        "        x = 1\n"
        "    except Exception:\n"
        "        pass\n"
        "def loop_shielded():\n"
        "    while True:\n"
        "        try:\n"
        "            x = 1\n"
        "        except Exception:\n"
        "            pass\n"
        "def spawn_all():\n"
        "    threading.Thread(target=naked).start()\n"
        "    threading.Thread(target=shielded, daemon=True).start()\n"
        "    threading.Thread(target=loop_shielded, daemon=True).start()\n"
        "    t = threading.Thread(target=shielded)\n"
        "    t.daemon = True\n"
        "    t.start()\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def stop(self):\n"
        "        self._t.join()\n"
        "    def _run(self):\n"
        "        try:\n"
        "            pass\n"
        "        except Exception:\n"
        "            pass\n"
    )}
    crash = _run(tmp_path, files, ["thread-crash-surface"])
    assert [f.line for f in crash] == [16], [f.render() for f in crash]
    daemon = _run(tmp_path, files, ["daemon-or-joined"])
    # line 16: naked() spawn is fire-and-forget without daemon; the
    # S._t thread is joined in stop() so only line 16 flags
    assert [f.line for f in daemon] == [16], [f.render() for f in daemon]


def test_metrics_discipline_fixture(tmp_path):
    files = {"tendermint_tpu/m.py": (
        "class M:\n"
        "    def __init__(self, r):\n"
        "        self.good = r.counter('s', 'a', '', labels=('x',))\n"
        "        self.bad = r.counter('s', 'b', '', labels=('x',))\n"
        "        self.plain = r.counter('s', 'c', '')\n"
        "        self.removed = r.gauge('s', 'd', '', labels=('p',))\n"
        "        self.good.add(0.0, x='k')\n"
        "    def gone(self, p):\n"
        "        self.removed.remove(p=p)\n"
    )}
    fs = _run(tmp_path, files, ["metrics-discipline"])
    assert [f.line for f in fs] == [4], [f.render() for f in fs]


_FAULTS_FIXTURE = (
    "CANONICAL_SITES: dict = {\n"
    "    'wal.write': 'x',\n"
    "    'p2p.send': 'y',\n"
    "}\n"
    "def fire(site):\n"
    "    pass\n"
)


def test_fault_site_registry_fixture(tmp_path):
    files = {
        "tendermint_tpu/utils/faults.py": _FAULTS_FIXTURE,
        "tendermint_tpu/m.py": (
            "from tendermint_tpu.utils import faults\n"
            "def f():\n"
            "    faults.fire('wal.write')\n"
            "    faults.fire('p2p.made_up')\n"
        ),
    }
    side = {"docs/FAULTS.md": "`wal.write` and `p2p.send` exist; "
                              "`p2p.stale_doc_site` does not\n"}
    fs = _run(tmp_path, files, ["fault-site-registry"], side)
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 2, [f.render() for f in fs]
    assert "p2p.made_up" in msgs[0] or "p2p.made_up" in msgs[1]
    assert any("stale_doc_site" in m for m in msgs)


_TRACE_FIXTURE = (
    "CANONICAL_SPANS = {\n"
    "    'consensus.commit': 'entered commit',\n"
    "    'verify.readback': 'blocking D2H fetch',\n"
    "}\n"
)


def test_trace_span_discipline_fixture(tmp_path):
    """must-trigger: an undeclared span literal, an undocumented
    canonical span, a stale doc token; must-not: a declared+documented
    span, a non-dotted literal (peerscore offences etc.), a foreign
    namespace in the doc."""
    files = {
        "tendermint_tpu/utils/trace.py": _TRACE_FIXTURE,
        "tendermint_tpu/m.py": (
            "def f(tr, board):\n"
            "    tr.mark('consensus.commit')\n"
            "    with tr.span('verify.made_up'):\n"
            "        pass\n"
            "    tr.record('verify.queue_typo', 1.0)\n"
            "    board.record('peerid', 'invalid_signature')\n"
        ),
    }
    side = {"docs/OBSERVABILITY.md": (
        "`consensus.commit` is documented; `verify.stale_doc_span` is "
        "stale; `other.namespace` is foreign\n")}
    fs = _run(tmp_path, files, ["trace-span-discipline"], side)
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 4, [f.render() for f in fs]
    assert any("verify.made_up" in m for m in msgs)
    assert any("verify.queue_typo" in m for m in msgs)
    assert any("verify.readback" in m and "not documented" in m
               for m in msgs)
    assert any("stale_doc_span" in m for m in msgs)
    assert not any("invalid_signature" in m or "other.namespace" in m
                   for m in msgs)


def test_config_knob_parity_fixture(tmp_path):
    undoc = _PFX + "FIXTURE_UNDOC"
    ghost = _CPFX + "FIXTURE_GHOST"
    documented = _PFX + "FIXTURE_OK"
    files = {"tendermint_tpu/m.py": (
        "import os\n"
        f"A = os.environ.get('{documented}')\n"
        f"B = os.environ.get('{undoc}')\n"
    )}
    side = {"docs/CONFIG.md": f"| `{documented}` | ok |\n| `{ghost}` | gone |\n"}
    fs = _run(tmp_path, files, ["config-knob-parity"], side)
    assert len(fs) == 2, [f.render() for f in fs]
    assert any(undoc in f.message and f.path.endswith("m.py") for f in fs)
    assert any(ghost in f.message and f.path.endswith("CONFIG.md")
               for f in fs)


def test_knob_parity_stale_doc_needs_full_default_scope(tmp_path):
    """A subset scan (e.g. `tmlint tendermint_tpu tests`) cannot see a
    knob read only in bench.py, so the doc->code 'stale doc' direction
    must stay quiet there — and still fire on a full-scope scan."""
    knob = _PFX + "BENCH_ONLY"
    for rel, content in {
        "tendermint_tpu/m.py": "x = 1\n",
        "bench.py": f"import os\nB = os.environ.get('{knob}')\n",
        "docs/CONFIG.md": f"| `{knob}` | bench knob |\n",
    }.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)
    sub = core.Project(str(tmp_path),
                       core.collect_files(str(tmp_path), ["tendermint_tpu"]))
    assert not core.run_rules(sub, ["config-knob-parity"])
    full = core.Project(
        str(tmp_path),
        core.collect_files(str(tmp_path), ["tendermint_tpu", "bench.py"]))
    # full scope sees the bench.py read, so parity holds cleanly too
    assert not core.run_rules(full, ["config-knob-parity"])
    # ...and a genuinely stale doc entry IS reported at full scope
    (tmp_path / "bench.py").write_text("x = 1\n")
    full2 = core.Project(
        str(tmp_path),
        core.collect_files(str(tmp_path), ["tendermint_tpu", "bench.py"]))
    fs = core.run_rules(full2, ["config-knob-parity"])
    assert any("stale doc" in f.message for f in fs), [f.render() for f in fs]


def test_pragma_inside_string_literal_is_inert(tmp_path):
    """Only real comments are pragmas: a pragma-shaped STRING (a fixture,
    a doc snippet) must not register a suppression."""
    files = {"tendermint_tpu/m.py": (
        "import threading\n"
        "import time\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._mtx = threading.Lock()\n"
        "    def bad(self):\n"
        "        with self._mtx:\n"
        "            x = '# tmlint: disable-file=lock-held-call'\n"
        "            time.sleep(1)\n"
        "            return x\n"
    )}
    fs = _run(tmp_path, files, ["lock-held-call"])
    assert [f.line for f in fs] == [9], [f.render() for f in fs]


def test_parse_error_is_a_finding(tmp_path):
    fs = _run(tmp_path, {"tendermint_tpu/m.py": "def broken(:\n"},
              ["lock-held-call"])
    assert _rules_of(fs) == {"parse-error"}


# ---------------------------------------------------------------------------
# pragmas + baseline
# ---------------------------------------------------------------------------


def test_pragma_suppresses_line_and_file(tmp_path):
    base = (
        "import threading\n"
        "import time\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._mtx = threading.Lock()\n"
        "    def bad(self):\n"
        "        with self._mtx:\n"
        "            time.sleep(1){pragma}\n"
    )
    hot = {"tendermint_tpu/m.py": base.format(pragma="")}
    cold = {"tendermint_tpu/m.py": base.format(
        pragma="  # tmlint: disable=lock-held-call")}
    wrong = {"tendermint_tpu/m.py": base.format(
        pragma="  # tmlint: disable=lock-order")}
    filewide = {"tendermint_tpu/m.py":
                "# tmlint: disable-file=lock-held-call\n"
                + base.format(pragma="")}
    assert _run(tmp_path / "a", hot, ["lock-held-call"])
    assert not _run(tmp_path / "b", cold, ["lock-held-call"])
    assert _run(tmp_path / "c", wrong, ["lock-held-call"])
    assert not _run(tmp_path / "d", filewide, ["lock-held-call"])


def test_pragma_on_line_above(tmp_path):
    files = {"tendermint_tpu/m.py": (
        "import threading\n"
        "import time\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._mtx = threading.Lock()\n"
        "    def bad(self):\n"
        "        with self._mtx:\n"
        "            # tmlint: disable=lock-held-call\n"
        "            time.sleep(1)\n"
    )}
    assert not _run(tmp_path, files, ["lock-held-call"])


def test_baseline_roundtrip(tmp_path):
    files = {"tendermint_tpu/m.py": (
        "import threading\n"
        "import time\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._mtx = threading.Lock()\n"
        "    def bad(self):\n"
        "        with self._mtx:\n"
        "            time.sleep(1)\n"
    )}
    fs = _run(tmp_path, files, ["lock-held-call"])
    assert fs
    bl = tmp_path / "baseline.txt"
    core.write_baseline(fs, str(bl))
    entries = core.load_baseline(str(bl))
    new, old = core.split_baselined(fs, entries)
    assert not new and len(old) == len(fs)
    # line drift does NOT invalidate a baseline entry (no line numbers in
    # the identity), a different message does
    moved = [core.Finding(f.path, f.line + 7, f.rule, f.message) for f in fs]
    new, old = core.split_baselined(moved, entries)
    assert not new
    other = [core.Finding(f.path, f.line, f.rule, f.message + "!") for f in fs]
    new, old = core.split_baselined(other, entries)
    assert len(new) == len(fs)


def test_unknown_rule_rejected(tmp_path):
    with pytest.raises(ValueError, match="unknown rule"):
        _run(tmp_path, {"tendermint_tpu/m.py": "x = 1\n"}, ["no-such-rule"])


# ---------------------------------------------------------------------------
# 3. lock-order witness units
# ---------------------------------------------------------------------------


@pytest.fixture
def own_witness():
    """Isolate these units from a session-wide TMTPU_LOCKWITNESS=1 sweep:
    swap in a fresh Witness (the deliberately planted cycle below must
    never poison the session graph or trip pytest_sessionfinish), then
    restore the session witness and re-arm the sweep."""
    saved = lockwitness.WITNESS
    sweep_active = saved.enabled
    lockwitness.uninstall()
    lockwitness.WITNESS = lockwitness.Witness()
    try:
        yield
    finally:
        lockwitness.uninstall()
        lockwitness.WITNESS = saved
        if sweep_active:
            lockwitness.install()


def test_witness_detects_opposite_order_cycle(own_witness):
    with lockwitness.witness(assert_on_exit=False) as w:
        a = threading.Lock()
        b = threading.Lock()

        def ab():
            with a:
                with b:
                    pass

        def ba():
            with b:
                with a:
                    pass

        t1 = threading.Thread(target=ab)
        t2 = threading.Thread(target=ba)
        t1.start(); t1.join()
        t2.start(); t2.join()
    cycles = w.cycles()
    assert cycles, f"no cycle found; edges={sorted(w.edges)}"
    with pytest.raises(AssertionError, match="lock-order cycle"):
        w.assert_acyclic()


def test_witness_consistent_order_is_acyclic(own_witness):
    with lockwitness.witness() as w:
        a = threading.Lock()
        b = threading.Lock()
        for _ in range(3):
            with a:
                with b:
                    pass
    assert w.acquires >= 6 and not w.cycles()


def test_witness_reentrant_rlock_not_a_cycle(own_witness):
    with lockwitness.witness() as w:
        r = threading.RLock()
        with r:
            with r:  # same instance: reentrancy, not ordering
                pass
    assert not w.cycles()


def test_witness_same_site_different_instances_is_flagged(own_witness):
    """Two locks born at the same line (per-peer locks) nested = the
    two-peers-in-opposite-order hazard; recorded as a site self-edge."""
    with lockwitness.witness(assert_on_exit=False) as w:
        locks = [threading.Lock() for _ in range(2)]  # one creation site
        with locks[0]:
            with locks[1]:
                pass
    assert w.cycles(), sorted(w.edges)


def test_witness_condition_wait_releases_held_entry(own_witness):
    """Condition.wait fully releases the RLock: the witness stack must
    drop it (a waiter does NOT hold the lock) and restore on wake."""
    with lockwitness.witness() as w:
        cond = threading.Condition()
        other = threading.Lock()
        done = []

        def waiter():
            with cond:
                cond.wait(timeout=5)
                done.append(True)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.1)
        # if wait() leaked a held entry, this nested take under `other`
        # would record cond->other AND other->cond edges across threads
        with other:
            with cond:
                cond.notify()
        t.join(timeout=5)
        assert done
    assert not w.cycles()


def test_witness_overhead_bookkeeping_bounded(own_witness):
    with lockwitness.witness() as w:
        locks = [threading.Lock() for _ in range(4)]
        for _ in range(200):
            for lk in locks:
                with lk:
                    pass
    assert not w.truncated
    assert w.max_depth <= 2
    assert w.acquires >= 800


def test_witness_uninstall_restores_factories(own_witness):
    before = threading.Lock
    with lockwitness.witness():
        assert threading.Lock is not before
    assert threading.Lock is lockwitness._REAL_LOCK
    assert threading.RLock is lockwitness._REAL_RLOCK
