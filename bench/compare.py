"""The comparison that decides ``correct``.

Each compared solve (the last one, and a seeded sample of those served
in the window) is held to the same table, landmarks and γ:

- ``spectrum_gap``: the widest gap between the k leading eigenvalues of
  the normalized Laplacian and ``reference.spectrum``'s, which does not
  depend on how the leading eigenspace happens to be rotated;
- ``embedding_gap``: how far each embedding row lies from a single linear
  map of its float64 affinity row, normalized (the extension pass, row
  by row; a lower tile precision fails it);
- ``impurity``: the share of clients the served partition puts outside
  their cluster's majority planted label (the extension and k-means
  together, against the labels the generator planted).

The serving answers are checked one by one (``serving_checks``): every
served cohort has the asked size and distinct ids in range, each in a
cluster of the served partition, and the served versions never go back.
The update path's guarantee is exact: every acknowledged row is read
back from the service's table, and the last solve is of the last table.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from bench import reference

#: numbers of a compared solve, each held to the cell's ``limits``
SOLVE_NUMBERS = ("spectrum_gap", "embedding_gap", "impurity")
#: rows the embedding's map is fitted on
FIT_ROWS = 65536
#: least variance, against the largest, of an affinity direction fitted
WHITEN_RCOND = 1e-12


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def spectrum_gap(evals_prog, evals_ref, k: int) -> float:
    a = np.asarray(evals_prog, np.float64)[:k]
    b = np.asarray(evals_ref, np.float64)[:k]
    if len(a) < k or len(b) < k:
        return float("inf")
    return float(np.max(np.abs(a - b)))


def _fit_rows(e: np.ndarray, ut: np.ndarray) -> np.ndarray:
    """The (K, k) map R whose ``ut @ R`` rows point most nearly along
    the rows of ``e``: the least generalized eigenvector of
    Σᵢ ‖(1 − eᵢeᵢᵀ) Rᵀuᵢ‖² against Σᵢ ‖Rᵀuᵢ‖², which is linear in R."""
    big, k = ut.shape[1], e.shape[1]
    z = (ut[:, :, None] * e[:, None, :]).reshape(len(e), big * k)
    h = np.kron(ut.T @ ut, np.eye(k))
    g = h - z.T @ z
    low = np.linalg.cholesky(h)
    a = np.linalg.solve(low, np.linalg.solve(low, g).T)
    _, vecs = np.linalg.eigh(0.5 * (a + a.T))
    return np.linalg.solve(low.T, vecs[:, 0]).reshape(big, k)


def embedding_gap(embedding, c: np.ndarray, seed: int) -> float:
    """RMS over all rows of the distance between the program's embedding
    row and the nearest unit row of the form cᵢR, one (m, k) R for all.

    Every Nyström extension is such a map of the affinity row: row i is
    Sᵢ W⁻¹ᐟ² V Λ⁻¹ᐟ² = cᵢ/√d̂ᵢ · P, normalized, and the scale 1/√d̂ᵢ
    drops out.  So the eigenbasis the program chose, rotated or mixed
    however rounding left it, is a choice of R and costs nothing here
    (``spectrum_gap`` and ``impurity`` hold it); what is paid is an
    error in a row's own affinities, as a lower tile precision makes.
    R is fitted on a seeded sample of rows, in the affinity's principal
    directions down to ``WHITEN_RCOND`` of the largest.
    """
    e = np.asarray(embedding, np.float64)
    u = np.asarray(c, np.float64)
    u = u / np.maximum(np.linalg.norm(u, axis=1, keepdims=True), 1e-300)
    rng = np.random.default_rng(seed)
    fit = rng.choice(len(e), min(len(e), FIT_ROWS), replace=False)
    var, vecs = np.linalg.eigh(u[fit].T @ u[fit])
    keep = var > WHITEN_RCOND * var.max()
    u = u @ (vecs[:, keep] / np.sqrt(var[keep]))
    y = u @ _fit_rows(e[fit], u[fit])
    y /= np.maximum(np.linalg.norm(y, axis=1, keepdims=True), 1e-300)
    if np.sum(e * y) < 0:
        y = -y
    return float(np.sqrt(np.mean(np.sum((e - y) ** 2, axis=1))))


def impurity(assign, labels) -> float:
    """Share of clients outside their cluster's majority planted label."""
    assign = np.asarray(assign)
    labels = np.asarray(labels)
    table = np.zeros((assign.max() + 1, labels.max() + 1), np.int64)
    np.add.at(table, (assign, labels), 1)
    return float(1.0 - table.max(axis=1).sum() / len(labels))


def compare_solve(result, table: np.ndarray, labels: np.ndarray,
                  landmark_idx, gamma: float, k: int,
                  seed: int = 0) -> Dict[str, float]:
    """Numbers of a program ``CohortResult`` against the reference and
    the planted labels of the same table."""
    idx = np.asarray(landmark_idx)
    c = reference.affinity(table, np.asarray(table)[idx], gamma,
                           reference.block_rows(len(idx)), np.float64)
    return {"spectrum_gap": spectrum_gap(
                result.evals, reference.spectrum(table, idx, gamma, c=c), k),
            "embedding_gap": embedding_gap(result.embedding, c, seed),
            "impurity": impurity(result.assign, labels)}


def control_numbers(table: np.ndarray, landmark_idx, gamma: float, k: int,
                    precision: str = "high") -> Dict[str, float]:
    """The reference with its products at a lower ``precision`` put in
    the program's place."""
    idx = np.asarray(landmark_idx)
    c = reference.affinity(table, np.asarray(table)[idx], gamma,
                           reference.block_rows(len(idx)))
    ref = reference.spectrum(table, idx, gamma, c=c)
    ctl = reference.spectrum(table, idx, gamma, precision=precision, c=c)
    return {"spectrum_gap": spectrum_gap(ctl, ref, k)}


def serving_checks(cohort_faults: int, version_regressions: int,
                   solver_errors: int, unread_rows: int, stale_final: int,
                   td_loss_nonfinite: int) -> list:
    """Exact checks of the serving path: each limit is 0."""
    return [Check("cohort_faults", cohort_faults, 0),
            Check("version_regressions", version_regressions, 0),
            Check("solver_errors", solver_errors, 0),
            Check("unread_rows", unread_rows, 0),
            Check("stale_final", stale_final, 0),
            Check("td_loss_nonfinite", td_loss_nonfinite, 0)]


def cohort_fault(ids, size: int, n: int, assign, k: int) -> bool:
    """A served cohort that is short, repeats a client, leaves range, or
    holds a client that the served partition puts in no cluster."""
    ids = np.asarray(ids)
    if len(ids) != min(size, n) or len(np.unique(ids)) != len(ids):
        return True
    if len(ids) and (ids.min() < 0 or ids.max() >= n):
        return True
    if len(np.asarray(assign)) != n:
        return True
    own = np.asarray(assign)[ids]
    return bool(len(own) and (own.min() < 0 or own.max() >= k))


def partition_fault(assign, k: int, n: int) -> bool:
    """A served partition that does not place every client in one of its
    ``k`` clusters."""
    assign = np.asarray(assign)
    return bool(len(assign) != n or assign.min() < 0 or assign.max() >= k)
