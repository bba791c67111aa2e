"""Share of the Nyström passes' device time that the chip's roofline
needs for one solve's work, counted once (``bench/roofline.py``)."""

from bench import roofline
from bench.metrics._common import traced_solves


def read(run):
    solves = traced_solves(run)
    if not solves or not run.trace.group_s["nystrom"]:
        return None
    c = run.config
    least, _ = roofline.nystrom_least_s(
        int(c["num_clients"]), int(c["num_landmarks"]), int(c["embed_dim"]),
        int(c["num_clusters"]), run.device_kind,
        c.get("engine", {}).get("affinity_dtype", "f32"))
    return 100.0 * least / (run.trace.group_s["nystrom"] / solves)
