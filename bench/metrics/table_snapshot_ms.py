"""Median ``cohort.snapshot`` span inside a ``cohort.select``: the copy of
the (N, d) table that applies the updates pending since the last
snapshot, on the select path."""

from bench import spans as S
from bench.metrics._common import ms


def read(run):
    return ms((s.seconds for s in S.named(S.spans_of(run), "cohort.snapshot")
               if s.parent is not None and s.parent.name == "cohort.select"),
              50)
