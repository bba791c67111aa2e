"""95th percentile over the traced updates of the wait from the end of
the ``cohort.mailbox`` span that published the first solve holding an
update to the end of the first ``cohort.swap`` that serves it: the
published solve waiting for a select (``bench/spans.py``,
``freshness_parts``)."""

from bench import spans as S
from bench.metrics._common import ms


def read(run):
    parts, _ = S.freshness_parts(S.spans_of(run))
    return ms((p["mailbox"] for p in parts), 95)
