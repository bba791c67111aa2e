"""Median select latency, each timed from when it was due (so queueing
behind a slow round counts), over every select of the window."""

from bench.metrics._common import ms


def read(run):
    return ms((s["done"] - s["due"] for s in run.selects), 50)
