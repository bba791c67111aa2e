"""95th percentile over the traced updates of the wait from the end of an
update's ``cohort.update`` span to the start of the first ``cohort.warm``
whose snapshot holds it: the solver's dirty set and the solve ahead of
it (``bench/spans.py``, ``freshness_parts``)."""

from bench import spans as S
from bench.metrics._common import ms


def read(run):
    parts, _ = S.freshness_parts(S.spans_of(run))
    return ms((p["queue"] for p in parts), 95)
