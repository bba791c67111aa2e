"""Median ``policy.train`` span: the host side of one online TD step in
``observe_round`` (replay sample, upload, dispatch; the step's device
work is waited on by the next select's ``policy.q``)."""

from bench import spans as S
from bench.metrics._common import ms


def read(run):
    return ms((s.seconds for s in S.named(S.spans_of(run), "policy.train")),
              50)
