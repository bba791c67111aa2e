"""The one traffic generator: planted client tables and the open-loop
schedules of a traffic mix, all drawn from ``--seed``.

A mix is a data file under ``bench/traffic/``; a configuration is one
under ``bench/configs/``.  Nothing here knows a cell by name.

Every seed gets the same amount of work: the select gaps are one fixed
set of exponential quantiles, laid out evenly (short and long gaps
interleaved by a golden-ratio sequence that the seed rotates), and
updates are due on a fixed grid.  What the seed changes is the table,
which clients report, and where the gap sequence starts.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def sub_seeds(seed: int, count: int) -> List[int]:
    """``count`` independent 31-bit seeds derived from any whole number."""
    state = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        count, np.uint32)
    return [int(s) & 0x7FFFFFFF for s in state]


def zipf_weights(count: int, s: float) -> np.ndarray:
    """Normalised Zipf(s) weights over ranks 1..count."""
    w = 1.0 / np.arange(1, count + 1, dtype=np.float64) ** s
    return w / w.sum()


@dataclasses.dataclass
class Population:
    """A planted table: cluster centres, each client's label, its rows."""
    centers: np.ndarray          # (k, d) float32
    weights: np.ndarray          # (k,) Zipf cluster shares
    labels: np.ndarray           # (n,) int64 planted cluster of each client
    table: np.ndarray            # (n, d) float32


def planted_table(n: int, k: int, d: int, seed: int, *, cluster_zipf: float,
                  center_scale: float) -> Population:
    """(n, d) clients around k planted centres with Zipf-skewed sizes.

    Centres ~ N(0, center_scale²), unit-variance scatter; cluster c holds
    a share ∝ 1/(c+1)^cluster_zipf of the clients.
    """
    rng = np.random.default_rng(seed)
    centers = (rng.standard_normal((k, d), dtype=np.float32)
               * np.float32(center_scale))
    weights = zipf_weights(k, cluster_zipf)
    labels = rng.choice(k, size=n, p=weights)
    table = centers[labels]
    table += rng.standard_normal((n, d), dtype=np.float32)
    return Population(centers, weights, labels, table)


@dataclasses.dataclass
class UpdateBatch:
    """One sketch-update batch: due time (s after window start), rows."""
    due: float
    ids: np.ndarray              # (b,) distinct client ids
    rows: np.ndarray             # (b, d) float32
    labels: np.ndarray           # (b,) planted cluster of each row


def update_stream(pop: Population, mix: dict, seconds: float, seed: int,
                  ) -> List[UpdateBatch]:
    """Update batches due in a window of ``seconds``, on a fixed grid.

    Client ids are Zipf(``id_zipf``) over a seeded ranking of the
    population (a few devices report most often).  Each row is redrawn
    around its client's planted centre; a share ``move_share`` of them
    first moves the client to a cluster drawn by the cluster shares (its
    data drifted).  Mutates ``pop.labels`` in step with the stream;
    ``pop.table`` is left as it was.
    """
    rate = float(mix["rate_per_s"])
    rows_per = int(mix["batch_rows"])
    count = int(round(rate * seconds))
    n, d = pop.table.shape
    k = len(pop.centers)
    rng = np.random.default_rng(seed)
    ranking = rng.permutation(n)
    cdf = np.cumsum(zipf_weights(n, float(mix["id_zipf"])))
    cdf[-1] = 1.0
    batches = []
    for j in range(count):
        ids = np.empty(0, np.int64)
        while len(ids) < rows_per:
            draw = ranking[np.searchsorted(cdf, rng.random(2 * rows_per))]
            ids = np.concatenate([ids, draw])
            _, first = np.unique(ids, return_index=True)
            ids = ids[np.sort(first)]
        ids = ids[:rows_per]
        moves = rng.random(rows_per) < float(mix["move_share"])
        pop.labels[ids[moves]] = rng.choice(k, size=int(moves.sum()),
                                            p=pop.weights)
        rows = (pop.centers[pop.labels[ids]]
                + rng.standard_normal((rows_per, d), dtype=np.float32))
        batches.append(UpdateBatch((j + 0.5) / rate, ids, rows,
                                   pop.labels[ids].copy()))
    return batches


#: select gap layouts a mix may name under ``selects.gaps``
GAP_LAYOUTS = ("even_exponential",)


def select_schedule(rate: float, seconds: float, seed: int,
                    gaps: str = "even_exponential") -> np.ndarray:
    """Due times of selects: round(rate·seconds) arrivals.

    ``even_exponential`` (the only layout): the gaps are the exponential distribution's quantiles at
    (i + ½)/count, scaled to end half a mean gap before the window does.
    Gap i takes the rank of frac(i·φ + u) among all i, u drawn from
    ``seed``: a seeded shuffle would put runs of short gaps together on
    some seeds and not others, and with a few dozen selects a window
    then reads the order more than the service.
    """
    if gaps not in GAP_LAYOUTS:
        raise ValueError(f"unknown select gap layout {gaps!r}; "
                         f"known: {GAP_LAYOUTS}")
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q)
    gaps *= seconds * (1.0 - 0.5 / count) / gaps.sum()
    u = np.random.default_rng(seed).random()
    golden = (np.sqrt(5.0) - 1.0) / 2.0
    rank = np.argsort(np.argsort(np.mod(np.arange(count) * golden + u, 1.0)))
    return np.cumsum(gaps[rank])


def accuracies(count: int, seed: int) -> np.ndarray:
    """Synthetic round accuracies reported through ``observe_round``."""
    return np.random.default_rng(seed).uniform(0.5, 0.9, count)
