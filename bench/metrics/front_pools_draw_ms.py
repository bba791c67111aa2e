"""Median per select of its ``cohort.pools`` span (the cluster pools as
lists of client ids) plus its ``policy.draw`` span (shuffling them and
the ε-greedy draw, with the Q values' trip to the host)."""

from bench import spans as S
from bench.metrics._common import ms


def read(run):
    return ms((sum(c.seconds for c in sel.children
                   if c.name in ("cohort.pools", "policy.draw"))
               for sel in S.named(S.spans_of(run), "cohort.select")), 50)
