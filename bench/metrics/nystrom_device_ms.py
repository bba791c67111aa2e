"""Device time per solve of the fused Nyström passes and W's affinity
(the kernels ``bench/kernels.json`` names for the group ``nystrom``)."""

from bench.metrics._common import traced_solves


def read(run):
    solves = traced_solves(run)
    if not solves:
        return None
    return run.trace.group_s["nystrom"] / solves * 1e3
