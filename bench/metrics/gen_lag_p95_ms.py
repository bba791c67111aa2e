"""95th percentile of how late the load generator issued each request:
an update after it was due, a select after it was due or, when the
previous round was still running, after that round ended (the wait
behind it counts in the select's latency, not here)."""

from bench.metrics._common import ms


def read(run):
    lags = [s["issue"] - s["ready"] for s in run.selects]
    lags += [u["issue"] - u["due"] for u in run.updates]
    return ms(lags, 95)
