"""``observe_ms`` in a cell whose select latency is not an end-to-end metric
(it spreads too widely there): the same reading, moving freshness."""

from bench.metrics.observe_ms import read  # noqa: F401
