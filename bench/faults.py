"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault takes ``patch(owner, name, value)`` (``setattr``, or pytest's
``monkeypatch.setattr``) and replaces one function of the program with a
broken one.  ``tests/bench`` plants each at a CPU size; ``bench/tune.py
limits --fault <name>`` reads them on the chip at the cell's size.
"""

from __future__ import annotations

import numpy as np


def stale_state(patch):
    """A solve that returns its state unchanged: every later solve hands
    back the first one."""
    from repro.cohort import engine
    orig = engine.CohortEngine._prepare
    first = []

    def stale(self, embeds, fp, *, key, warm_ok):
        if not first:
            first.append(orig(self, embeds, fp, key=key, warm_ok=warm_ok))
        return first[0]

    patch(engine.CohortEngine, "_prepare", stale)


def half_batch(patch):
    """Half of the rows left out of the operator; the second half of the
    embedding copied from the first."""
    from repro.cohort import sharded
    orig = sharded.sharded_nystrom_from_landmarks

    def half(x, idx, k, gamma, mesh, **kw):
        n = x.shape[0]
        y, evals, basis, w_basis = orig(x[: n // 2], idx, k, gamma, mesh,
                                        **kw)
        y = np.concatenate([np.asarray(y), np.asarray(y)])[:n]
        return y, evals, basis, w_basis

    patch(sharded, "sharded_nystrom_from_landmarks", half)


def altered_answer(patch):
    """One client of each cohort replaced by another of the same cohort."""
    from repro.policy import cluster_policy
    orig = cluster_policy.ClusterPolicy.draw

    def altered(self, rng, state, pools, size):
        picked, actions = orig(self, rng, state, pools, size)
        return picked[:-1] + picked[:1], actions

    patch(cluster_policy.ClusterPolicy, "draw", altered)


def wrong_extension(patch):
    """The embedding rows of one client in ten handed to other clients,
    the spectrum left as it was."""
    from repro.cohort import sharded
    orig = sharded.sharded_nystrom_from_landmarks

    def shifted(x, idx, k, gamma, mesh, **kw):
        y, evals, basis, w_basis = orig(x, idx, k, gamma, mesh, **kw)
        y = np.array(y)
        rows = np.arange(0, len(y), 10)
        y[rows] = y[np.roll(rows, 1)]
        return y, evals, basis, w_basis

    patch(sharded, "sharded_nystrom_from_landmarks", shifted)


def wrong_kmeans(patch):
    """k-means whose assignments are dealt out at random, cluster sizes
    kept, the embedding and spectrum left as they were."""
    from repro.cohort import engine
    orig = engine.kmeans

    def dealt(key, y, k, *args, **kw):
        assign, centers = orig(key, y, k, *args, **kw)
        assign = np.asarray(assign)
        return np.random.default_rng(0).permutation(assign), centers

    patch(engine, "kmeans", dealt)


FAULTS = {f.__name__: f for f in (stale_state, half_batch, altered_answer,
                                  wrong_extension, wrong_kmeans)}
