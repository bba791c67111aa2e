"""Median wall time of ``observe_round`` (policy state, replay, one TD
step), timed by the harness."""

from bench.metrics._common import ms


def read(run):
    return ms(run.observes, 50)
