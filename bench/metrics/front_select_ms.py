"""Median of the service's own ``last_select_s`` over the window's
selects: snapshot, mailbox swap, cluster pools, policy state, draw."""

from bench.metrics._common import ms


def read(run):
    return ms((s["front_s"] for s in run.selects), 50)
