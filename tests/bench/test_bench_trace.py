"""The benchmark's reduction from a profiler trace to device numbers:
on hand-made planes where every number is known, and on a small trace
recorded on a TPU v5e chip (``fixtures/small.xplane.pb``)."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", ".."))

from bench import trace  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "small.xplane.pb")
GROUPS = {"nystrom": {"ops": ["nystrom_colsum_pallas",
                                  "nystrom_extension_pallas"],
                      "count_by": "nystrom_extension_pallas"},
          "kmeans": {"modules": ["jit_kmeans"]},
          "allreduce": {"ops": ["all-reduce"]}}


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def hand_planes():
    # chip 0: ops 100-200 (colsum), 150-250 (fusion, overlaps), 400-500
    # (extension), 600-700 (kmeans program), window 0-1000
    # (a consumer names the kernel among its operands: not a kernel op)
    colsum = "%nystrom_colsum_pallas.1 = f32[1,64] custom-call(f32[8] %x)"
    consumer = "%fusion.1 = f32[8] fusion(f32[1,64] %nystrom_colsum_pallas.1)"
    ext = "%nystrom_extension_pallas.2 = f32[8,8] custom-call(f32[8] %x)"
    dev0 = plane("/device:TPU:0",
                 XLA_Ops=[ev(colsum, 100, 100), ev(consumer, 150, 100),
                          ev(ext, 400, 100),
                          ev("%fusion.2 = f32[8] fusion()", 600, 100)],
                 XLA_Modules=[ev("jit_body(1)", 90, 420),
                              ev("jit_kmeans(2)", 590, 120)])
    dev1 = plane("/device:TPU:1",
                 XLA_Ops=[ev("%all-reduce.3 = f32[64] all-reduce()", 0,
                             300)],
                 XLA_Modules=[])
    host = plane("/host:CPU",
                 bench_thread=[ev("bench.select", 250, 200),
                               ev("bench.update", 720, 50)])
    return [dev0, dev1, host]


def test_reduce_on_hand_made_planes():
    s = trace.reduce(hand_planes(), (0.0, 1000.0), 1, GROUPS)
    assert s.window_s == pytest.approx(1000e-9)
    # union of [100, 250], [400, 500], [600, 700]
    assert s.busy_s == pytest.approx(350e-9)
    assert s.group_s["nystrom"] == pytest.approx(200e-9)
    assert s.group_s["kmeans"] == pytest.approx(100e-9)
    assert s.group_s["allreduce"] == 0.0
    assert s.group_runs["nystrom"] == 1
    assert sorted(s.device_ops) == [
        ("jit_body/fusion.1", pytest.approx(100e-9)),
        ("jit_body/nystrom_colsum_pallas.1", pytest.approx(100e-9)),
        ("jit_body/nystrom_extension_pallas.2", pytest.approx(100e-9)),
        ("jit_kmeans/fusion.2", pytest.approx(100e-9))]
    # idle 0-100, 250-400 (inside the select span), 500-600, 700-1000
    # (the update span), longest first
    gaps = [(name, round(sec * 1e9)) for name, sec in s.idle_gaps]
    assert gaps == [("update", 300), ("select", 150), ("none", 100),
                    ("none", 100)]


def test_two_chips_average_busy_and_collectives():
    s = trace.reduce(hand_planes(), (0.0, 1000.0), 2, GROUPS)
    assert s.busy_s == pytest.approx((350e-9 + 300e-9) / 2)
    assert s.group_s["allreduce"] == pytest.approx(300e-9 / 2)


def test_window_clips_events():
    s = trace.reduce(hand_planes(), (120.0, 450.0), 1, GROUPS)
    assert s.busy_s == pytest.approx((250 - 120 + 450 - 400) * 1e-9)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no fixture")
def test_recorded_tpu_trace():
    planes = list(trace.load_planes(FIXTURE))
    s = trace.reduce(planes, trace.window_of(planes), 1, trace.name_table())
    assert 0 < s.busy_s < s.window_s
    assert s.group_runs["nystrom"] >= 1
    assert s.group_s["nystrom"] > 0 and s.group_s["kmeans"] > 0
    assert len(s.device_ops) == 10 and len(s.idle_gaps) == 10
    assert any(name != "none" for name, _ in s.idle_gaps)
