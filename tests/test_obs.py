"""The cohort service's spans (``repro.obs``) in a profiler trace.

A small streaming DQN server runs a few updates, selects and observes
under ``jax.profiler`` on the CPU; the trace is read back with the
benchmark's own reduction (``bench/spans.py``).  Checked: the span tree
(parents by nesting on each thread), the ``seq`` and ``version`` links
from an update through the warm solve and the mailbox to the select that
serves it, the counters the spans mirror, and that ``SPAN_NAMES`` lists
exactly the names the program emits.
"""

import os
import sys
import time
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import spans as S  # noqa: E402
from bench import spec, trace  # noqa: E402
from repro import obs  # noqa: E402
from repro.cohort import CohortConfig  # noqa: E402
from repro.launch.serve import CohortServer  # noqa: E402
from repro.streaming import StreamingSpec  # noqa: E402

N, D, K = 2048, 8, 4

#: each span's allowed parents (None: opened outside any program span)
PARENTS = {
    "cohort.select": {None},
    "cohort.snapshot": {"cohort.select", "cohort.warm"},
    "cohort.swap": {"cohort.select"},
    "cohort.inline_solve": {"cohort.select"},
    "cohort.pools": {"cohort.select"},
    "policy.state": {"cohort.select", "cohort.observe"},
    "policy.draw": {"cohort.select"},
    "policy.q": {"policy.draw"},
    "cohort.account": {"cohort.select"},
    "cohort.observe": {None},
    "policy.observe": {"cohort.observe"},
    "policy.train": {"cohort.observe"},
    "cohort.update": {None},
    "cohort.flush": {"cohort.update"},
    "solver.task": {None},
    "cohort.warm": {"solver.task"},
    "cohort.mailbox": {"cohort.warm"},
    "engine.prepare": {"cohort.warm", "cohort.inline_solve"},
    "engine.publish": {"cohort.warm", "cohort.inline_solve"},
    "engine.fingerprint": {"engine.prepare"},
    "engine.sketch": {"engine.prepare"},
    "engine.upload": {"engine.prepare"},
    "engine.landmarks": {"engine.prepare"},
    "engine.kmeans": {"engine.prepare"},
    "engine.wait": {"engine.prepare", "engine.landmarks"},
}


def _wait(pred, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.005)


def _trace(out, body):
    jax.profiler.start_trace(out)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return S.program_spans(list(trace.load_planes(trace.find_xplane(out))))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The streaming server, its spans, the versions its selects served,
    and the spans of a plain server's inline solve (traced apart)."""
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(K, D)) * 6
    table = (centers[rng.integers(0, K, N)]
             + rng.normal(size=(N, D))).astype(np.float32)
    srv = CohortServer(N, D, seed=0, policy="dqn", state_features="rich",
                       config=CohortConfig(num_clusters=K, method="nystrom"),
                       streaming=StreamingSpec())
    served = []

    def update_and_wait():
        warmed = srv.stats()["warm_ahead"]
        ids = rng.choice(N, 64, replace=False)
        srv.update_embeddings(ids, table[ids] + 0.05)
        _wait(lambda: srv.stats()["warm_ahead"] > warmed)

    def streaming():
        srv.update_embeddings(np.arange(N), table)   # a forced flush
        _wait(lambda: srv.stats()["warm_ahead"] >= 1)
        for r in range(3):
            update_and_wait()
            if r == 1:
                update_and_wait()   # published over an unserved solve
            srv.select_cohort(32)
            served.append(srv.stats()["streaming"]["served_version"])
            srv.observe_round(0.5 + 0.1 * r)
        srv.close(timeout=120)

    def inline():
        plain = CohortServer(256, D, seed=1, policy="stratified",
                             config=CohortConfig(num_clusters=K))
        plain.update_embeddings(np.arange(256), table[:256])
        plain.select_cohort(16)

    spans = _trace(str(tmp_path_factory.mktemp("streaming")), streaming)
    plain = _trace(str(tmp_path_factory.mktemp("inline")), inline)
    return srv, spans, served, plain


def test_span_names_are_exactly_the_catalog(traced):
    _, spans, _, plain = traced
    assert {s.name for s in spans + plain} == set(obs.SPAN_NAMES)
    assert set(PARENTS) == set(obs.SPAN_NAMES)
    with pytest.raises(ValueError):
        obs.span("cohort.unknown")


def test_span_tree(traced):
    _, spans, _, plain = traced
    for s in spans + plain:
        parent = None if s.parent is None else s.parent.name
        assert parent in PARENTS[s.name], (s.name, parent)
    (inline,) = S.named(plain, "cohort.select")
    assert [c.name for c in inline.children] == [
        "cohort.snapshot", "cohort.inline_solve", "cohort.pools",
        "policy.draw", "cohort.account"]
    assert [c.name for c in inline.children[1].children] == [
        "engine.prepare", "engine.publish"]
    dqn = S.named(spans, "cohort.select")
    assert len(dqn) == 3
    for s in dqn:
        assert [c.name for c in s.children] == [
            "cohort.snapshot", "cohort.swap", "cohort.pools",
            "policy.state", "policy.draw", "cohort.account"]
        assert s.stats["requests"] == 1 and s.stats["lock_wait_ns"] >= 0
    for t in S.named(spans, "solver.task"):
        assert [c.name for c in t.children] == ["cohort.warm"]
        assert t.stats["queued_ns"] >= 0 and t.stats["coalesced"] >= 0
    for w in S.named(spans, "cohort.warm"):
        assert [c.name for c in w.children] == [
            "cohort.snapshot", "engine.prepare", "engine.publish",
            "cohort.mailbox"]
        assert w.stats["adopted"] == 0
    for p in S.named(spans + plain, "engine.prepare"):
        assert p.stats["n"] in (N, 256) and not p.stats["cached"]
        assert {w.stats["what"] for w in p.within("engine.wait")} >= {
            "result", "state"}
    # a whole-table update forces the flush (both servers start so)
    assert [f.parent.stats["rows"] for f in S.named(
        spans + plain, "cohort.flush")] == [N, 256]


def test_seq_and_version_links(traced):
    srv, spans, served, _ = traced
    dqn = S.named(spans, "cohort.select")
    observes = S.named(spans, "cohort.observe")
    assert [s.stats["seq"] for s in dqn] == [1, 2, 3]
    assert [o.stats["seq"] for o in observes] == [1, 2, 3]
    # the select's served version is the one stats() reports after it
    assert [s.stats["served"] for s in dqn] == served
    for s in dqn:
        (swap,) = s.within("cohort.swap")
        assert swap.stats["served"] == s.stats["served"] == \
            s.stats["version"]
    # update -> warm -> mailbox, one table generation per version
    for w in S.named(spans, "cohort.warm"):
        (mail,) = w.within("cohort.mailbox")
        assert mail.stats["version"] == w.stats["version"]
    versions = [u.stats["version"] for u in S.named(spans, "cohort.update")]
    assert versions == list(range(1, len(versions) + 1))
    parts, cut = S.freshness_parts(spans)
    assert len(parts) == len(versions) and cut == 0
    for p in parts:
        assert p["queue"] + p["solve"] + p["mailbox"] + p["tail"] == \
            pytest.approx(p["age"])


def test_counters_match_spans(traced):
    srv, spans, _, _ = traced
    st = srv.stats()
    # v1 and v3 were published over by v2 and v4 before any select
    replaced = [m.stats["replaced"] for m in S.named(spans, "cohort.mailbox")]
    assert replaced == [0, 1, 0, 1, 0]
    assert st["superseded"] == sum(replaced)
    assert S.publish_fates(spans) == (3, 2, 0)
    # the forced flush, then what each snapshot applied
    rows = sum(s.stats["rows"] for s in S.named(spans, "cohort.snapshot"))
    assert st["streaming"]["rows_materialized"] == N + rows


def test_policy_state_spans_mark_memo_hits(traced):
    """Each select serves a newly published solve and builds the state's
    per-solve half (``hit`` = 0); its observe reuses it (``hit`` = 1).
    The spans agree with the counters and with ``state_hit_share``."""
    srv, spans, _, plain = traced
    sel = [s.within("policy.state")[0].stats["hit"]
           for s in S.named(spans, "cohort.select")]
    obs_ = [o.within("policy.state")[0].stats["hit"]
            for o in S.named(spans, "cohort.observe")]
    assert sel == [0, 0, 0] and obs_ == [1, 1, 1]
    st = srv.stats()
    assert (st["state_stats_builds"], st["state_stats_hits"]) == (3, 3)
    assert spec.reader("state_hit_share")(
        NS(trace=NS(spans=spans))) == pytest.approx(50.0)
    # a stratified server builds no state
    assert not S.named(plain, "policy.state")


def test_pool_spans_mark_index_hits(traced, tmp_path):
    """Each select of the traced server serves a newly published solve
    and builds its cluster index (``hit`` = 0 on ``cohort.pools``), as
    does the plain server's inline solve; selects that serve one solve
    again reuse it (``hit`` = 1).  The spans agree with the counters."""
    srv, spans, _, plain = traced
    assert [s.within("cohort.pools")[0].stats["hit"]
            for s in S.named(spans, "cohort.select")] == [0, 0, 0]
    st = srv.stats()
    assert (st["pool_index_builds"], st["pool_index_hits"]) == (3, 0)
    (inline,) = S.named(plain, "cohort.select")
    assert inline.within("cohort.pools")[0].stats["hit"] == 0

    rng = np.random.default_rng(1)
    quiet = CohortServer(256, D, seed=2, policy="stratified",
                         config=CohortConfig(num_clusters=K),
                         streaming=StreamingSpec())

    def body():
        quiet.update_embeddings(
            np.arange(256), rng.normal(size=(256, D)).astype(np.float32))
        _wait(lambda: quiet.stats()["warm_ahead"] >= 1)
        for _ in range(3):
            quiet.select_cohort(16)
        quiet.close(timeout=120)

    hits = [s.within("cohort.pools")[0].stats["hit"]
            for s in S.named(_trace(str(tmp_path), body), "cohort.select")]
    assert hits == [0, 1, 1]
    st = quiet.stats()
    assert (st["pool_index_builds"], st["pool_index_hits"]) == (1, 2)
