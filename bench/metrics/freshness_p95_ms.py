"""95th percentile over the window's update batches of the time from a
batch's acknowledgement to the completion of the first select that
served its version or a later one.  A batch still unserved when the
window closes counts at its age then."""

import numpy as np


def read(run):
    if not run.updates or not run.selects:
        return None
    done = np.array([s["done"] for s in run.selects])
    served = np.array([s["version"] for s in run.selects], np.float64)
    # the first select in completion order whose served version >= v
    order = np.argsort(done)
    done, served = done[order], np.maximum.accumulate(served[order])
    ages = []
    for u in run.updates:
        hit = np.flatnonzero((served >= u["version"]) & (done >= u["ack"]))
        end = done[hit[0]] if len(hit) else run.close_s
        ages.append(end - u["ack"])
    return float(np.percentile(ages, 95)) * 1e3
