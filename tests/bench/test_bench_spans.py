"""The program-span readers (``bench/spans.py`` and the ``program_span``
metrics in ``bench/metrics/``) on hand-made planes where every number is
known, and the span-named idle gaps against ``trace.reduce``."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", ".."))
sys.path.insert(0, HERE)

from bench import spans as S  # noqa: E402
from bench import spec, trace  # noqa: E402
from test_bench_trace import FIXTURE, GROUPS, hand_planes  # noqa: E402

NEW = ("policy_state_ms", "front_pools_draw_ms", "td_step_ms",
       "table_snapshot_ms", "solve_queue_p95_ms", "mailbox_wait_p95_ms",
       "served_solve_share", "solve_host_ms")


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=list(stats.items()))


def service_planes():
    """One select thread, one update thread, one solver thread, a chip.

    Updates v1-v3 are solved by warms v1-v3; v4 has no warm in the
    window (cut).  Select 1 serves v1; select 2 serves v3, so v2's
    publish was superseded; v5's publish comes after the last select
    (cut).  Select 3 has no observe in the window.
    """
    select = [
        ev("cohort.select", 1000, 2000, seq=1, version=1, served=1),
        ev("cohort.snapshot", 1000, 1100, rows=64),
        ev("cohort.swap", 1100, 1150, served=1),
        ev("cohort.pools", 1150, 1400), ev("policy.state", 1400, 1600),
        ev("policy.draw", 1600, 1900), ev("policy.q", 1700, 1800),
        ev("cohort.account", 1900, 1950),
        ev("cohort.observe", 2000, 2600, seq=1),
        ev("policy.state", 2000, 2200), ev("policy.observe", 2200, 2250),
        ev("policy.train", 2250, 2600),
        ev("cohort.select", 5000, 6000, seq=2, version=3, served=3),
        ev("cohort.snapshot", 5000, 5200, rows=0),
        ev("cohort.swap", 5200, 5300, served=3),
        ev("cohort.pools", 5300, 5500), ev("policy.state", 5500, 5800),
        ev("policy.draw", 5800, 5900), ev("cohort.account", 5900, 5950),
        ev("cohort.observe", 6000, 6500, seq=2),
        ev("policy.state", 6000, 6400), ev("policy.train", 6400, 6500),
        ev("cohort.select", 8000, 9000, seq=3, version=4, served=3),
        ev("cohort.swap", 8000, 8050, served=3),
        ev("cohort.pools", 8050, 8150), ev("policy.draw", 8150, 8350),
        # the harness's own spans are not the program's
        ev("bench.select", 1000, 2000), ev("bench.observe", 2000, 2600),
        ev("bench.select", 5000, 6000), ev("bench.observe", 6000, 6500),
    ]
    update = [ev("cohort.update", 100, 200, version=1, rows=64),
              ev("cohort.update", 300, 400, version=2, rows=64),
              ev("cohort.update", 2500, 2600, version=3, rows=64),
              ev("bench.update", 2500, 2600),
              ev("cohort.update", 7000, 7100, version=4, rows=64)]
    solver = [
        ev("solver.task", 250, 900, queued_ns=50, coalesced=0),
        ev("cohort.warm", 260, 900, version=1, adopted=0),
        ev("cohort.snapshot", 260, 300, rows=64),
        ev("engine.prepare", 300, 800, cached=0),
        ev("engine.wait", 600, 700, what="result"),
        ev("engine.wait", 750, 780, what="state"),
        ev("engine.publish", 800, 850),
        ev("cohort.mailbox", 850, 900, version=1, replaced=0),
        ev("solver.task", 1000, 1900, queued_ns=600, coalesced=0),
        ev("cohort.warm", 1000, 1900, version=2, adopted=0),
        ev("engine.prepare", 1050, 1700, cached=0),
        ev("engine.landmarks", 1100, 1450),
        ev("engine.wait", 1200, 1400, what="gamma"),
        ev("cohort.mailbox", 1850, 1900, version=2, replaced=0),
        ev("solver.task", 2700, 4000, queued_ns=100, coalesced=1),
        ev("cohort.warm", 2700, 4000, version=3, adopted=0),
        ev("engine.prepare", 2750, 3800, cached=0),
        ev("engine.wait", 3000, 3500, what="result"),
        ev("cohort.mailbox", 3900, 4000, version=3, replaced=1),
        ev("cohort.warm", 9400, 9600, version=5, adopted=0),
        ev("engine.prepare", 9400, 9450, cached=1),
        ev("cohort.mailbox", 9500, 9600, version=5, replaced=0),
    ]
    chip = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%fusion.1 = f32[8] fusion()", 0, 1000),
                                   ev("%fusion.2 = f32[8] fusion()", 2100,
                                      2400),
                                   ev("%fusion.3 = f32[8] fusion()", 6600,
                                      10000)]),
        NS(name="XLA Modules", events=[])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=select),
                                       NS(name="python", events=update),
                                       NS(name="python", events=solver)])
    return [chip, host]


@pytest.fixture
def spans():
    return S.program_spans(service_planes(), (0.0, 10000.0))


def read(metric, spans):
    return spec.reader(metric)(NS(trace=NS(spans=spans)))


def test_spans_nest_per_thread(spans):
    # the harness's bench.* spans are not the program's
    assert all(s.name.startswith(S.prefixes()) for s in spans)
    assert len(spans) == 52                  # of 57 events
    (q,) = S.named(spans, "policy.q")
    assert [q.parent.name, q.parent.parent.name] == ["policy.draw",
                                                    "cohort.select"]
    assert q.depth == 2 and q.parent.parent.parent is None
    (gamma,) = [w for w in S.named(spans, "engine.wait")
                if w.stats["what"] == "gamma"]
    assert gamma.parent.name == "engine.landmarks"
    first = S.named(spans, "cohort.select")[0]
    assert [c.name for c in first.children] == [
        "cohort.snapshot", "cohort.swap", "cohort.pools", "policy.state",
        "policy.draw", "cohort.account"]
    # a span on another thread never nests, however the times fall
    (warm,) = [w for w in S.named(spans, "cohort.warm")
               if w.stats["version"] == 2]
    assert warm.parent.name == "solver.task"
    assert all(c.thread == warm.thread for c in warm.within("engine.wait"))


def test_window_drops_spans_it_cuts(spans):
    cut = S.program_spans(service_planes(), (1050.0, 10000.0))
    assert len(S.named(cut, "cohort.select")) == 2
    assert len(S.named(cut, "cohort.update")) == 2


def test_freshness_parts_add_up_to_each_age(spans):
    parts, cut = S.freshness_parts(spans)
    assert cut == 1                          # v4: no warm in the window
    ns = [{k: round(v * 1e9) for k, v in p.items()} for p in parts]
    assert ns == [
        dict(queue=60, solve=640, mailbox=250, tail=850, age=1800),
        dict(queue=600, solve=900, mailbox=3400, tail=700, age=5600),
        dict(queue=100, solve=1300, mailbox=1300, tail=700, age=3400)]
    for p in parts:
        assert p["queue"] + p["solve"] + p["mailbox"] + p["tail"] == \
            pytest.approx(p["age"])


def test_publish_fates(spans):
    # v1 and v3 served, v2 superseded by v3, v5 after the last select
    assert S.publish_fates(spans) == (2, 1, 1)


@pytest.mark.parametrize("metric, ns", [
    ("policy_state_ms", 550),        # rounds 400 and 700; seq 3 cut
    ("front_pools_draw_ms", 300),    # 550, 300, 300
    ("td_step_ms", 225),             # 350, 100
    ("table_snapshot_ms", 150),      # 100, 200; the warm's not counted
    ("solve_queue_p95_ms", 550),     # 60, 100, 600
    ("mailbox_wait_p95_ms", 3190),   # 250, 1300, 3400
    ("solve_host_ms", 450),          # 370, 450, 550; the cached one out
])
def test_span_readers(spans, metric, ns):
    assert read(metric, spans) == pytest.approx(ns * 1e-6)


def test_served_solve_share(spans):
    assert read("served_solve_share", spans) == pytest.approx(200 / 3)


@pytest.mark.parametrize("metric", NEW)
def test_readers_find_nothing_without_program_spans(metric):
    """What a program without spans gives: no value, and no error."""
    assert spec.reader(metric)(NS(trace=None)) is None
    assert read(metric, []) is None
    planes = hand_planes()
    assert read(metric, S.program_spans(planes, (0.0, 1000.0))) is None


def test_new_metrics_are_declared_per_cell():
    bench = spec.benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert m["source"] == "program_span" and len(m["workloads"]) == 1


def test_idle_gaps_named_by_program_spans():
    planes = service_planes()
    window = (0.0, 10000.0)
    gaps = S.idle_gaps(planes, window, S.program_spans(planes, window))
    assert [(n, round(s * 1e9)) for n, s in gaps] == [
        ("observe+select+update", 4200),
        ("observe+select / cohort.select, engine.prepare", 1100)]
    # the harness label and length are what trace.reduce gives
    s = trace.reduce(planes, window, 1, GROUPS)
    assert [(n.split(" / ")[0], d) for n, d in gaps] == s.idle_gaps


def test_trace_without_program_spans_keeps_todays_labels():
    planes = hand_planes()
    assert S.program_spans(planes, (0.0, 1000.0)) == []
    s = trace.reduce(planes, (0.0, 1000.0), 1, GROUPS)
    assert s.idle_gaps == [("update", pytest.approx(300e-9)),
                           ("select", pytest.approx(150e-9)),
                           ("none", pytest.approx(100e-9)),
                           ("none", pytest.approx(100e-9))]
    assert S.idle_gaps(planes, (0.0, 1000.0), []) == s.idle_gaps


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no fixture")
def test_recorded_tpu_trace_has_todays_gaps():
    planes = list(trace.load_planes(FIXTURE))
    window = trace.window_of(planes)
    s = trace.reduce(planes, window, 1, trace.name_table())
    assert S.program_spans(planes, window) == []
    assert S.idle_gaps(planes, window, []) == s.idle_gaps
