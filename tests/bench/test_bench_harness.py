"""The benchmark harness on the CPU at a tiny size.

The cell loop runs for a couple of seconds through the harness's own
functions and forms a well-shaped result line; the command itself
refuses to run without a TPU; and with the timed path broken underneath
(``bench/faults.py``: a solve that returns its state unchanged, half of
the rows left out of the operator, an answer altered where it is
produced, embedding rows handed to other clients, k-means assignments
dealt at random) and with the program's bfloat16 tiles, the control,
``correct`` comes out false.
"""

import copy
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import faults, harness, run, spec  # noqa: E402

SEED = 2**33 + 12345          # seeds may be wider than 32 bits


def tiny(workload: str = "xdevice-1m.churn", rate: float = 3.0):
    """The cell at 4,096 clients (above the engine's dense cutoff, so the
    sharded fused path runs), 4 clusters, cohorts of 32."""
    cell = spec.load_cell(workload)
    cell.config = dict(cell.config, num_clients=4096, num_clusters=4,
                       cohort_size=32)
    cell.cell = dict(cell.cell, select_rate_per_s=rate)
    cell.traffic = copy.deepcopy(cell.traffic)
    if cell.traffic.get("updates"):
        cell.traffic["updates"].update(batch_rows=64)
    return cell


def line_of(cell, seconds=1.5, seed=SEED):
    out = harness.run_cell(cell, seed, seconds, False,
                           process_start=time.perf_counter())
    out["device_count"] = 1
    return run.result_line(out, cell, False), out


def test_cell_loop_forms_the_result_line():
    cell = tiny()
    line, out = line_of(cell)
    assert json.loads(json.dumps(line)) == line
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    names = {m["name"]: m["unit"] for m in cell.end_to_end}
    assert set(line["metrics"]) == set(names)
    for name, metric in line["metrics"].items():
        assert metric["unit"] == names[name]
        assert np.isfinite(metric["value"]) and metric["value"] > 0
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}
    assert out["compiles_in_window"] == 0
    assert out["compared_solves"] >= 1


def test_quiet_cell_runs_no_solve_in_the_window():
    cell = tiny("xdevice-1m.quiet")
    line, out = line_of(cell, seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"select_p50_ms", "setup_s"}
    assert not out["run"].solves


def test_same_seed_same_inputs():
    from bench import traffic
    a = traffic.planted_table(500, 4, 8, 7, cluster_zipf=1.1,
                              center_scale=6.0)
    b = traffic.planted_table(500, 4, 8, 7, cluster_zipf=1.1,
                              center_scale=6.0)
    np.testing.assert_array_equal(a.table, b.table)
    mix = {"batch_rows": 16, "rate_per_s": 10, "id_zipf": 1.1,
           "move_share": 0.5}
    ua = traffic.update_stream(a, mix, 1.0, 3)
    ub = traffic.update_stream(b, mix, 1.0, 3)
    assert len(ua) == 10
    for x, y in zip(ua, ub):
        np.testing.assert_array_equal(x.ids, y.ids)
        np.testing.assert_array_equal(x.rows, y.rows)
        assert len(np.unique(x.ids)) == len(x.ids) == 16
    s1 = traffic.select_schedule(2.0, 30.0, 5)
    s2 = traffic.select_schedule(2.0, 30.0, 6)
    assert len(s1) == len(s2) == 60 and s1[-1] < 30.0
    np.testing.assert_allclose(np.sort(np.diff(s1, prepend=0)),
                               np.sort(np.diff(s2, prepend=0)))
    with pytest.raises(ValueError):
        traffic.select_schedule(2.0, 30.0, 5, "poisson")


def test_command_refuses_a_host_without_a_tpu(capsys):
    assert run.main(["--workload", "xdevice-1m.churn", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


# -- the timed path broken underneath: correct has to come out false ------

@pytest.mark.parametrize("fault, failing", [
    (faults.stale_state, "stale_final"),
    (faults.half_batch, "spectrum_gap"),
    (faults.altered_answer, "cohort_faults"),
    (faults.wrong_extension, "embedding_gap"),
    (faults.wrong_kmeans, "impurity"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, failing):
    fault(monkeypatch.setattr)
    line, _ = line_of(tiny(), seconds=1.5)
    assert line["correct"] is False
    check = line["checks"][failing]
    assert check["value"] > check["limit"], line["checks"]


def test_the_program_s_bf16_tiles_are_not_correct():
    """The control: the program's own lower-precision path (bfloat16
    affinity tiles) driven through the whole run."""
    cell = tiny()
    cell.config = dict(cell.config, engine=dict(cell.config["engine"],
                                                affinity_dtype="bf16"))
    line, _ = line_of(cell, seconds=1.5)
    assert line["correct"] is False
    check = line["checks"]["embedding_gap"]
    assert check["value"] > check["limit"], line["checks"]
