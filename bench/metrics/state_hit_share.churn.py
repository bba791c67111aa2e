"""``state_hit_share`` in a cell whose select latency is not an end-to-end
metric: the same reading, moving freshness."""

from bench.metrics.state_hit_share import read  # noqa: F401
