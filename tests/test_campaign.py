"""Campaign runner + repro minimization (tendermint_tpu/e2e/campaign.py,
docs/SOAK.md §campaigns).

Quick tier: the ddmin minimizer against synthetic failure predicates
(injected run_fn — no clusters), violation-signature parsing, coverage
gap-fill determinism, and artifact schema arithmetic on a stubbed phase
runner.

Slow tier: a real two-phase generated campaign over a durable fabric
(zero violations, full vocabulary coverage census) and the forced-failure
path — an intentionally unhealed quorum crash whose five-entry schedule
auto-minimizes to exactly the two quorum-cutting crash entries.
"""

import json

import pytest

from tendermint_tpu.e2e import campaign
from tendermint_tpu.e2e.soak import SoakAction, SoakSchedule
from tendermint_tpu.utils import faults, nemesis

SEED = 2026


@pytest.fixture(autouse=True)
def _clean_chaos():
    faults.configure([], seed=SEED)
    nemesis.clear()
    yield
    nemesis.clear()
    nemesis.PLANE.on_heal.clear()
    faults.clear()


# ---------------------------------------------------------------------------
# ddmin minimizer units (quick, synthetic run_fn)
# ---------------------------------------------------------------------------


def test_minimize_finds_interacting_pair():
    calls = []

    def run_fn(sub):
        calls.append(list(sub))
        return "b" in sub and "e" in sub

    sub, runs = campaign.minimize(list("abcdefgh"), run_fn, max_runs=40)
    assert sorted(sub) == ["b", "e"]
    assert runs == len(calls) <= 40
    # every probe the minimizer accepted still reproduces: the returned
    # subset is FAILING by construction, never a guess
    assert run_fn(sub)


def test_minimize_single_culprit_and_order_preserved():
    sub, _ = campaign.minimize(list("abcdef"), lambda s: "d" in s,
                               max_runs=40)
    assert sub == ["d"]
    # order of surviving entries is schedule order, not ddmin visit order
    sub, _ = campaign.minimize(
        list("abcdef"), lambda s: "b" in s and "e" in s, max_runs=40)
    assert sub == ["b", "e"]


def test_minimize_run_cap_returns_failing_superset():
    """A cap hit must return a subset that STILL fails (best-so-far),
    never a half-reduced guess that might pass."""
    entries = list("abcdefghij")

    def run_fn(sub):
        return "a" in sub

    sub, runs = campaign.minimize(entries, run_fn, max_runs=2)
    assert runs <= 2
    assert run_fn(sub), "cap-hit result must still reproduce"


def test_minimize_degenerate_inputs():
    assert campaign.minimize(["x"], lambda s: True, max_runs=5)[0] == ["x"]
    assert campaign.minimize([], lambda s: True, max_runs=5)[0] == []


def test_violation_kind_parsing():
    assert campaign._violation_kind("[liveness @12.3s] no commit") == "liveness"
    assert campaign._violation_kind("[false-expiry @1s] x") == "false-expiry"
    assert campaign._violation_kind("[bft-time @0.5s] y") == "bft-time"
    assert campaign._violation_kind("garbage") == "unknown"


def test_last_phase_parsing():
    v = ("[liveness @12.3s] no node committed a block for 8.0s "
         "[lagging: node 1@h4 last_phase=consensus.precommit(h4), "
         "node 2@h0 last_phase=?]")
    assert campaign._last_phases(v) == {
        "1": "consensus.precommit(h4)", "2": "?"}
    assert campaign._last_phases("[liveness @1s] bare detail") == {}


# ---------------------------------------------------------------------------
# Coverage gap-fill (quick)
# ---------------------------------------------------------------------------


def test_gap_actions_speak_the_schedule_grammar():
    """Every injectable gap action must round-trip through the soak
    grammar — a gap-filled schedule IS a repro line."""
    for kind in campaign.VOCABULARY:
        a = campaign._gap_action(kind, 5.0, 3)
        assert a is not None, kind
        assert SoakAction.parse(a.describe()).kind == kind


def test_fill_gaps_targets_uncovered_vocabulary():
    base = SoakSchedule([SoakAction(2.0, "partition", "1|rest", 1.0)])
    filled = campaign.fill_gaps(base, {"crash": 1}, 20.0, seed=7, nodes=5)
    kinds = [a.kind for a in filled.actions]
    assert "partition" in kinds
    # injected kinds come from the uncovered vocabulary only
    injected = [k for k in kinds if k != "partition"]
    assert injected and all(k not in ("partition", "crash")
                            for k in injected)
    assert len(injected) <= 3
    # deterministic in (seed, covered): replay re-derives the same fill
    again = campaign.fill_gaps(base, {"crash": 1}, 20.0, seed=7, nodes=5)
    assert again.describe() == filled.describe()
    # nothing missing -> untouched schedule
    full = {k: 1 for k in campaign.VOCABULARY}
    assert campaign.fill_gaps(base, full, 20.0, 7, 5).describe() == \
        base.describe()


def test_injected_crash_always_tears_the_wal_tail():
    a = campaign._gap_action("crash", 5.0, 2)
    assert a.arg.endswith(":torn"), \
        "campaign gap-fill guarantees torn-tail coverage"


# ---------------------------------------------------------------------------
# Real campaigns (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_campaign_two_phases_clean_with_coverage(tmp_path):
    art = campaign.run_campaign(str(tmp_path), seed=3, budget_s=40.0,
                                phase_s=16.0, nodes=5,
                                liveness_budget_s=25.0,
                                out=str(tmp_path / "SOAK.json"))
    assert art["violations"] == [], art["violations"]
    assert art["version"] == campaign.SCHEMA_VERSION
    assert len(art["phases"]) >= 2
    assert len(art["coverage"]) >= 6, art["coverage"]
    assert art["stats"]["heights_audited"] > 0
    assert art["stats"]["max_height"] >= 2
    on_disk = json.loads((tmp_path / "SOAK.json").read_text())
    assert on_disk == art


@pytest.mark.slow
def test_campaign_minimizes_unhealed_quorum_crash(tmp_path):
    """The forced-failure path end to end: three noise entries plus two
    never-rebooted crashes that cut quorum on a 4-node cluster. The
    campaign must record a liveness violation and ddmin the schedule
    down to EXACTLY the two crash entries — a replayable repro line."""
    spec = ("@2:linkfault~1:*>1:drop%0.3;@3:power:2:15;@4:skew~3:3:60;"
            "@6:crash~-1:1;@6.5:crash~-1:2")
    art = campaign.run_campaign(str(tmp_path), seed=9, budget_s=30.0,
                                phase_s=18.0, nodes=4,
                                liveness_budget_s=7.0,
                                phase_specs=[spec], max_minimize_runs=8)
    assert art["violations"]
    assert art["violations"][0]["kind"] == "liveness"
    assert art["violations"][0]["phase"] == 0
    mini = art["minimized_repro"]
    assert mini.startswith("TMTPU_SOAK_REPRO:")
    assert "TMTPU_SOAK_DURABLE=1" in mini
    sched = mini.split("TMTPU_SOAK_SCHEDULE='")[1].rstrip("'")
    assert sched == "@6:crash~-1:1;@6.5:crash~-1:2", mini
