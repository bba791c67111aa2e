"""repro-lint: fixture corpus, suppressions, baseline, watchdog, dogfood.

Tier-1.  The analyzer itself is stdlib-only (``repro.analysis`` imports
no jax), so most of this file runs in milliseconds; the dogfood
regression tests at the bottom exercise the real serving classes.
"""

import json
import pathlib
import threading

import pytest

from repro.analysis import (LockOrderError, OrderedLock, RULES,
                            SERVING_LOCK_ORDER, analyze_paths, instrument)
from repro.analysis.findings import (Finding, Suppressions, apply_baseline,
                                     load_baseline, save_baseline)
from repro.analysis.runner import main as lint_main
from repro.analysis import watchdog

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "analysis_fixtures"


def run_lint(*relpaths):
    return analyze_paths([str(FIXTURES / p) for p in relpaths], root=REPO)


def rules_of(findings):
    return {f.rule for f in findings}


# -- rule catalog ----------------------------------------------------------

def test_every_rule_documented():
    assert len(RULES) >= 11
    for rule, desc in RULES.items():
        assert rule == rule.lower() and " " not in rule
        assert len(desc) > 20


# -- purity / PRNG family --------------------------------------------------

def test_purity_bad_flags_every_rule():
    fs = run_lint("purity_bad.py")
    assert rules_of(fs) == {"jax-host-time", "jax-host-random",
                            "jax-host-sync", "prng-constant-key",
                            "prng-key-reuse", "jax-blocking-sync"}


def test_purity_bad_specific_sites():
    fs = run_lint("purity_bad.py")
    by_rule = {}
    for f in fs:
        by_rule.setdefault(f.rule, []).append(f)
    # three distinct sync shapes: .item(), float(), np.asarray()
    assert len(by_rule["jax-host-sync"]) == 3
    # stdlib random + np.random
    assert len(by_rule["jax-host-random"]) == 2
    # reachability: _helper is flagged although not itself decorated
    assert any(f.symbol == "_helper" for f in by_rule["jax-host-time"])
    # the blocking sync names the jitted producer line
    (block,) = by_rule["jax-blocking-sync"]
    assert block.symbol == "hot_path" and "float" in block.message


def test_purity_good_is_clean():
    assert run_lint("purity_good.py") == []


def test_purity_flags_host_spans_in_traced_code():
    fs = run_lint("purity_span.py")
    assert rules_of(fs) == {"jax-host-time"}
    # the two spans in the jitted body and the one in its helper; the
    # span around the call into it is host code and stays clean
    assert sorted((f.symbol, f.line) for f in fs) == [
        ("_helper", 19), ("spans_in_trace", 10), ("spans_in_trace", 12)]


# -- pallas family ---------------------------------------------------------

def test_pallas_bad_flags_all_three_rules():
    fs = run_lint("pallas_bad")
    assert rules_of(fs) == {"pallas-interpret", "pallas-static-args",
                            "pallas-ref-oracle"}
    oracle = next(f for f in fs if f.rule == "pallas-ref-oracle")
    assert "shift_ref" in oracle.message


def test_pallas_good_is_clean():
    assert run_lint("pallas_good") == []


# -- lock family -----------------------------------------------------------

def test_locks_bad_flags_guard_and_cycle():
    fs = run_lint("locks_bad.py")
    assert rules_of(fs) == {"lock-guarded-by", "lock-order-cycle"}
    guards = [f for f in fs if f.rule == "lock-guarded-by"]
    # plain assignment AND container-mutator call, but NOT the held
    # one — and exactly one finding per site (no Subscript/Attribute
    # double report)
    assert sorted(g.symbol for g in guards) == [
        "BadServer.unguarded_mutation", "BadServer.unguarded_mutator_call"]
    cycle = next(f for f in fs if f.rule == "lock-order-cycle")
    assert "_a_lock" in cycle.message and "_b_lock" in cycle.message


def test_locks_good_is_clean():
    assert run_lint("locks_good.py") == []


# -- suppressions ----------------------------------------------------------

def test_suppressions_silence_listed_rules_only():
    fs = run_lint("suppressed.py")
    # the only surviving finding is the one whose suppression names a
    # different rule
    assert [(f.rule, f.symbol) for f in fs] == [
        ("jax-host-time", "wrong_rule_listed")]


def test_suppression_comment_only_line_covers_next_line():
    s = Suppressions("# repro-lint: ignore[some-rule]\nx = 1\n")
    assert s.covers(1, "some-rule") and s.covers(2, "some-rule")
    assert not s.covers(2, "other-rule")


# -- baseline --------------------------------------------------------------

def test_baseline_add_and_expire_roundtrip(tmp_path):
    findings = run_lint("purity_bad.py")
    assert findings
    path = tmp_path / "baseline.json"
    save_baseline(path, findings)
    baseline = load_baseline(path)
    assert len(baseline) == len(findings)

    # grandfathered: nothing new, nothing stale
    new, stale = apply_baseline(findings, baseline)
    assert new == [] and stale == []

    # a fresh finding is new; a fixed finding leaves a stale entry
    extra = Finding(rule="jax-host-time", path="x.py", line=1,
                    message="m", symbol="f", source="t = time.time()")
    new, stale = apply_baseline(findings[1:] + [extra], baseline)
    assert new == [extra]
    assert [e["fingerprint"] for e in stale] == [
        findings[0].fingerprint()]


def test_baseline_fingerprint_survives_line_churn():
    a = Finding(rule="r", path="p.py", line=10, message="m",
                symbol="f", source="x = 1")
    b = Finding(rule="r", path="p.py", line=99, message="m (moved)",
                symbol="f", source="x = 1")
    assert a.fingerprint() == b.fingerprint()


def test_runner_check_mode_end_to_end(tmp_path, capsys):
    bad = str(FIXTURES / "purity_bad.py")
    base = str(tmp_path / "b.json")
    # no baseline: findings -> exit 1
    assert lint_main([bad, "--check", "--baseline", base]) == 1
    # grandfather them, then --check passes
    assert lint_main([bad, "--update-baseline", "--baseline", base]) == 0
    assert lint_main([bad, "--check", "--baseline", base]) == 0
    # --json emits a machine-readable summary
    capsys.readouterr()                       # drain the text output
    assert lint_main([bad, "--json", "--baseline", base]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["findings"] == []
    assert payload["baselined"] == payload["total"] > 0


def test_repo_src_is_clean_against_committed_baseline():
    """The dogfooded tree must lint clean (CI runs the same gate)."""
    findings = analyze_paths(["src"], root=REPO)
    baseline = load_baseline(REPO / ".repro-lint-baseline.json")
    new, _ = apply_baseline(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)
    assert len(baseline) <= 5          # acceptance: tiny or empty


# -- runtime watchdog ------------------------------------------------------

def test_ordered_lock_allows_declared_order():
    a = OrderedLock("a", 10)
    b = OrderedLock("b", 20)
    with a:
        with b:
            assert watchdog.held_names() == ["a", "b"]
    assert watchdog.held_names() == []


def test_ordered_lock_rejects_inversion_and_reentry():
    a = OrderedLock("a", 10)
    b = OrderedLock("b", 20)
    with b:
        with pytest.raises(LockOrderError):
            a.acquire()
    with a:
        with pytest.raises(LockOrderError):   # equal rank == reentry
            a.acquire()
    # stacks unwound cleanly after the failures
    assert watchdog.held_names() == []


def test_ordered_lock_is_per_thread():
    # held stacks are thread-local: while the main thread holds a
    # rank-20 lock, another thread may still start at rank 10 (with its
    # own lock instances — a shared global stack would raise here)
    b = OrderedLock("b", 20)
    a2, b2 = OrderedLock("a2", 10), OrderedLock("b2", 20)
    errors = []

    def other():
        try:
            with a2:
                with b2:
                    pass
        except Exception as e:          # pragma: no cover
            errors.append(e)

    with b:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert errors == []


def test_instrument_swaps_lock_attributes():
    class Obj:
        def __init__(self):
            self._write_lock = threading.Lock()
            self._select_lock = threading.Lock()
            self.not_a_lock = 3

    o = Obj()
    done = instrument(o, prefix="t0:")
    assert sorted(done) == ["_select_lock", "_write_lock"]
    assert isinstance(o._write_lock, OrderedLock)
    assert o._write_lock.rank == SERVING_LOCK_ORDER["_write_lock"]
    assert o.not_a_lock == 3
    with o._select_lock:
        with o._write_lock:             # declared order: select < write
            pass
    with pytest.raises(LockOrderError):
        with o._write_lock:
            with o._select_lock:
                pass
