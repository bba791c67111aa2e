"""Helpers shared by the metric readers (not a metric itself)."""

import numpy as np


def ms(values, q):
    """The q-th percentile of seconds, in milliseconds; None if empty."""
    values = list(values)
    return float(np.percentile(values, q)) * 1e3 if values else None


def traced_solves(run):
    """Solves whose Nyström passes ran in the traced window, or 0."""
    if run.trace is None:
        return 0
    return run.trace.group_runs.get("nystrom", 0)
