"""The ``state_hit_share`` readers on hand-made spans: the share of
``policy.state`` spans marked ``hit`` = 1, and nothing from a program
whose spans carry no ``hit``."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", ".."))
sys.path.insert(0, HERE)

from bench import spans as S  # noqa: E402
from bench import spec  # noqa: E402
from test_bench_spans import ev, service_planes  # noqa: E402

METRICS = ("state_hit_share", "state_hit_share.churn")


def rounds(*hits):
    """A select thread: per entry, a select and an observe whose
    ``policy.state`` spans carry ``hit`` (None: no such attribute)."""
    events, t = [], 0
    for seq, (sel, obs) in enumerate(hits, 1):
        events += [ev("cohort.select", t, t + 100, seq=seq),
                   ev("policy.state", t + 10, t + 60,
                      **({} if sel is None else {"hit": sel})),
                   ev("cohort.observe", t + 100, t + 200, seq=seq),
                   ev("policy.state", t + 100, t + 150,
                      **({} if obs is None else {"hit": obs}))]
        t += 1000
    host = NS(name="/host:CPU", lines=[NS(name="python", events=events)])
    return S.program_spans([host])


def read(metric, spans):
    return spec.reader(metric)(NS(trace=NS(spans=spans)))


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("hits, share", [
    (((1, 1), (1, 1)), 100.0),          # quiet: one solve served throughout
    (((0, 1), (0, 1), (1, 1)), 400 / 6),  # churn: a new solve per select
    (((0, 1),), 50.0),
])
def test_share_of_policy_state_spans_that_hit(metric, hits, share):
    assert read(metric, rounds(*hits)) == pytest.approx(share)


@pytest.mark.parametrize("metric", METRICS)
def test_readers_find_nothing_without_hit(metric):
    """A program without the memo marks no span: no value, no error."""
    assert read(metric, rounds((None, None), (None, None))) is None
    assert read(metric, []) is None
    assert spec.reader(metric)(NS(trace=None)) is None
    # test_bench_spans' service planes: policy.state spans, none with hit
    spans = S.program_spans(service_planes(), (0.0, 10000.0))
    assert S.named(spans, "policy.state")
    assert read(metric, spans) is None


def test_state_hit_metrics_are_declared_per_cell():
    declared = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name, moves, cell in (
            ("state_hit_share", "select_p50_ms", "xdevice-1m.quiet"),
            ("state_hit_share.churn", "freshness_p95_ms",
             "xdevice-1m.churn")):
        m = declared[name]
        assert (m["source"], m["layer"], m["unit"], m["better"]) == (
            "program_span", "policy", "%", "higher")
        assert m["moves"] == moves and m["workloads"] == [cell]
