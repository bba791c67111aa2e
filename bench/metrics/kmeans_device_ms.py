"""Device time per solve of the ``kmeans`` program."""

from bench.metrics._common import traced_solves


def read(run):
    solves = traced_solves(run)
    if not solves or not run.trace.group_s["kmeans"]:
        return None
    return run.trace.group_s["kmeans"] / solves * 1e3
