"""``select_p50_ms`` in a cell where it spreads too widely to be held to
a bound end to end: the same reading, moving freshness."""

from bench.metrics.select_p50_ms import read  # noqa: F401
