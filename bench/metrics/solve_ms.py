"""Median ``CohortResult.seconds`` of the solves published in the
window (host clock around the engine's whole solve)."""

from bench.metrics._common import ms


def read(run):
    return ms((s["seconds"] for s in run.solves if s["t"] <= run.window_s),
              50)
