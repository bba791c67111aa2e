"""Evaluation criteria for the paper's Table 3, plus serving-state stats.

Balanced accuracy, accuracy, macro recall, Cohen's kappa, macro one-vs-rest
AUC (rank-based, no sklearn), plus the "feature rate" (the paper's term;
we read it as macro precision, the closest standard quantity).

Also home to :func:`cluster_policy_state` — the per-cluster
participation/accuracy statistics the serving path feeds the DQN policy
(``repro.policy.ClusterPolicy``) as its state vector.  Two feature sets
are supported (the ``features`` knob, mirrored by
``CohortServer(state_features=...)``):

* ``"basic"`` — the original ``3k + 1`` layout: population fraction ‖
  participation fraction ‖ reward EMA ‖ previous accuracy.  Kept for
  replay-buffer back-compat: checkpointed/replayed transitions recorded
  against the narrow state keep their shape.
* ``"rich"``  — ``5k + 1``: the basic features plus per-cluster
  embedding **dispersion** (how spread out each cluster is around its
  centroid, relative to the global spread) and **staleness** (how many
  selects since each cluster last contributed a client to a served
  cohort).  This is the serving analogue of the simulation state's
  cluster centroids — the served DQN sees cohesion and recency, not
  just participation bookkeeping.
* ``"system"`` — ``7k + 1``: the rich features plus per-cluster
  **availability** (EMA of the completed/dropped outcome of each
  cluster's served clients, from ``repro.fed.realism`` round outcomes)
  and **mean latency** (EMA of simulated round-trip seconds, squashed
  to [0, 1)).  This is what lets the served DQN learn to route cohort
  slots away from slow or flaky clusters, not just skewed ones.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

#: recognised feature sets for :func:`cluster_policy_state`.
STATE_FEATURES = ("basic", "rich", "system")

#: per-cluster feature count of each layout (+1 for prev_accuracy).
_FEATURES_PER_CLUSTER = {"basic": 3, "rich": 5, "system": 7}


def serving_state_dim(k: int, features: str = "rich") -> int:
    """State-vector length of :func:`cluster_policy_state`.

    ``3k + 1`` for ``"basic"`` (population / participation / reward EMA
    + previous accuracy), ``5k + 1`` for ``"rich"`` (+ dispersion and
    staleness per cluster), ``7k + 1`` for ``"system"`` (+ availability
    and mean-latency per cluster).
    """
    if features not in STATE_FEATURES:
        raise ValueError(f"unknown state features {features!r}; "
                         f"expected one of {STATE_FEATURES}")
    return _FEATURES_PER_CLUSTER[features] * k + 1


def _check_per_cluster(name: str, arr: np.ndarray, k: int) -> np.ndarray:
    """Validate a per-cluster stat vector: must cover all k clusters.

    A silently short array used to be truncated by ``[:k]`` into a
    wrong-length state that only failed much later, inside the DQN's
    first matmul.  Fail here instead, naming the offending argument.
    Longer arrays are still sliced to ``[:k]`` (callers that track
    stats for a historical k̂ > k keep working).
    """
    arr = np.asarray(arr, np.float64).reshape(-1)
    if len(arr) < k:
        raise ValueError(
            f"cluster_policy_state: {name} has length {len(arr)} but "
            f"k={k} clusters; per-cluster stats must cover every "
            f"cluster (pad missing clusters with zeros upstream)")
    return arr[:k]


#: rows per partial sum in :func:`cluster_dispersion`: each cluster's sums
#: are accumulated over blocks of this many rows, then the blocks added,
#: which keeps a 10^6-row sum ~10^3 times closer to the exact one than
#: one running sum over every row.
_SUM_BLOCK = 1024


def cluster_dispersion(embeds: np.ndarray, assign: np.ndarray,
                       k: int) -> np.ndarray:
    """Per-cluster embedding spread, scale-free and bounded to [0, 1).

    For each cluster: the mean squared distance of its members to the
    cluster centroid, divided by the global mean squared distance to the
    global centroid, squashed through ``x / (1 + x)``.  Empty clusters
    report 0.  A tight cluster sits near 0; one as diffuse as the whole
    table sits near 0.5; a cluster wider than the table tends to 1.

    One float64 pass over the table: per-cluster counts, row sums and
    squared-norm sums of the rows centred on the global mean (so a large
    common offset does not cancel), each cluster's spread being its mean
    squared norm less its centroid's squared norm.
    """
    assign = np.asarray(assign)
    n = len(assign)
    out = np.zeros(k, np.float64)
    if n == 0:
        return out
    # (d, n): each coordinate contiguous for bincount's weights
    xt = np.asarray(np.asarray(embeds).T, np.float64, order="C")
    xt -= xt.mean(axis=1, keepdims=True)
    sq = np.einsum("ij,ij->j", xt, xt)
    global_var = float(sq.mean())
    if global_var <= 0.0:
        return out
    # bin of each row: (row block, cluster); ids outside [0, k) go to a
    # spare last bin, as they belong to no cluster
    bins = -(-n // _SUM_BLOCK) * k
    block = np.where((assign >= 0) & (assign < k),
                     np.arange(n) // _SUM_BLOCK * k + assign, bins)

    def per_cluster(weights=None):
        return np.bincount(block, weights=weights, minlength=bins)[
            :bins].reshape(-1, k).sum(axis=0)

    count = per_cluster()
    sums = np.stack([per_cluster(row) for row in xt], axis=1)
    sq_sums = per_cluster(sq)
    seen = count > 0
    c = count[seen]
    var = np.maximum(sq_sums[seen] / c - np.einsum(
        "ij,ij->i", sums[seen], sums[seen]) / (c * c), 0.0)
    ratio = var / global_var
    out[seen] = ratio / (1.0 + ratio)
    return out


def cluster_solve_stats(assign: np.ndarray, embeds: Optional[np.ndarray],
                        k: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """The per-solve half of :func:`cluster_policy_state`.

    ``(population, dispersion)``: each cluster's share of the ``n``
    clients, and :func:`cluster_dispersion` of ``embeds`` (``None`` when
    ``embeds`` is ``None``, as the ``"basic"`` layout needs none).  Both
    depend only on the solve's ``assign`` and the table it clustered, so
    a server builds them once per solve it serves and passes them back
    as ``cluster_policy_state(..., solve_stats=)``.
    """
    assign = np.asarray(assign)
    pop = np.bincount(assign, minlength=k)[:k] / max(len(assign), 1)
    disp = None if embeds is None else cluster_dispersion(embeds, assign, k)
    return pop, disp


def cluster_policy_state(assign: np.ndarray, k: int,
                         participation: np.ndarray,
                         reward_ema: np.ndarray,
                         prev_accuracy: float,
                         *,
                         embeds: Optional[np.ndarray] = None,
                         staleness: Optional[np.ndarray] = None,
                         availability: Optional[np.ndarray] = None,
                         latency_s: Optional[np.ndarray] = None,
                         features: str = "rich",
                         solve_stats: Optional[Tuple] = None) -> np.ndarray:
    """Serving-side DQN state: per-cluster stats + last global accuracy.

    Args:
        assign:        (n,) cluster ids in [0, k) from Algorithm I.
        k:             number of clusters (the DQN action count).
        participation: (k,) cumulative count of cohort slots served from
                       each cluster so far.
        reward_ema:    (k,) exponential moving average of the round
                       reward credited to draws from each cluster.
        prev_accuracy: global-model accuracy after the last round.
        embeds:        (n, d) embedding table behind ``assign``; required
                       for ``features="rich"``/``"system"`` (dispersion)
                       unless ``solve_stats`` is given.
        staleness:     (k,) count of selects since each cluster last
                       contributed a client to a served cohort; required
                       for ``features="rich"``/``"system"``.
        availability:  (k,) EMA in [0, 1] of each cluster's served
                       clients completing their round (vs dropping);
                       required for ``features="system"``.
        latency_s:     (k,) EMA of each cluster's simulated round-trip
                       seconds; required for ``features="system"``.
        features:      ``"basic"`` (3k + 1) | ``"rich"`` (5k + 1) |
                       ``"system"`` (7k + 1).
        solve_stats:   the per-solve half, ``cluster_solve_stats(assign,
                       embeds, k)``, built earlier for this same solve;
                       None builds it here.  ``CohortServer`` memoizes it
                       per served solve (keyed on the ``assign`` and
                       table objects), so a select or observe on a solve
                       already described skips the O(n·d) pass.

    Returns:
        float32 vector ``[population_frac ‖ participation_frac ‖
        reward_ema ( ‖ dispersion ‖ staleness_frac ( ‖ availability ‖
        latency_frac )) ‖ prev_accuracy]`` — population fraction is each
        cluster's share of clients, participation fraction its share of
        all slots served (uniform 1/k before any draw, so round 0 is
        not a degenerate all-zeros state), staleness and latency
        squashed to [0, 1) via ``x / (1 + x)``.
    """
    if features not in STATE_FEATURES:
        raise ValueError(f"unknown state features {features!r}; "
                         f"expected one of {STATE_FEATURES}")
    rich = features in ("rich", "system")
    if solve_stats is None:
        if rich and embeds is None:
            raise ValueError(
                f"cluster_policy_state: features={features!r} needs the "
                "embedding table (embeds=) for the dispersion features; "
                "pass features='basic' for the participation-only state")
        solve_stats = cluster_solve_stats(assign, embeds if rich else None,
                                          k)
    pop, dispersion = solve_stats
    participation = _check_per_cluster("participation", participation, k)
    reward = _check_per_cluster("reward_ema", reward_ema, k)
    total = participation.sum()
    part = (participation / total) if total > 0 else np.full(k, 1.0 / k)
    parts = [pop, part, reward]
    if rich:
        if dispersion is None:
            raise ValueError(
                f"cluster_policy_state: features={features!r} needs the "
                "dispersion in solve_stats (build it with embeds=)")
        if staleness is None:
            raise ValueError(
                f"cluster_policy_state: features={features!r} needs the "
                "per-cluster staleness counts (staleness=)")
        stale = _check_per_cluster("staleness", staleness, k)
        parts.append(dispersion)
        parts.append(stale / (1.0 + stale))
    if features == "system":
        if availability is None or latency_s is None:
            raise ValueError(
                "cluster_policy_state: features='system' needs the "
                "per-cluster availability (availability=) and mean "
                "latency (latency_s=) EMAs — the client-realism "
                "features from repro.fed.realism round outcomes")
        avail = np.clip(
            _check_per_cluster("availability", availability, k), 0.0, 1.0)
        lat = np.maximum(
            _check_per_cluster("latency_s", latency_s, k), 0.0)
        parts.append(avail)
        parts.append(lat / (1.0 + lat))
    parts.append([prev_accuracy])
    return np.concatenate(parts).astype(np.float32)


def confusion(y_true: np.ndarray, y_pred: np.ndarray, k: int) -> np.ndarray:
    cm = np.zeros((k, k), np.int64)
    np.add.at(cm, (y_true, y_pred), 1)
    return cm


def _midranks(scores: np.ndarray) -> np.ndarray:
    """1-based midranks: tied scores share the mean of their positions.

    The double-argsort trick assigns ties arbitrary *ordinal* ranks
    (whichever came first in memory wins), which biases the
    Mann–Whitney U statistic whenever logits tie — e.g. saturated
    softmax outputs or integer-ish scores.  Midranks are the standard
    tie correction: AUC under ties is then the probability of a correct
    ranking with ties counted as 1/2.
    """
    order = np.argsort(scores, kind="stable")
    sorted_scores = scores[order]
    n = len(scores)
    ranks = np.empty(n, np.float64)
    i = 0
    while i < n:
        j = i
        while j < n and sorted_scores[j] == sorted_scores[i]:
            j += 1
        ranks[i:j] = 0.5 * (i + j - 1) + 1.0     # mean of 1-based i+1..j
        i = j
    out = np.empty(n, np.float64)
    out[order] = ranks
    return out


def classification_metrics(y_true: np.ndarray, logits: np.ndarray) -> dict:
    k = logits.shape[-1]
    y_pred = np.argmax(logits, axis=-1)
    cm = confusion(y_true, y_pred, k)
    total = cm.sum()
    acc = np.trace(cm) / max(total, 1)

    per_class_recall = np.divide(np.diag(cm), cm.sum(axis=1),
                                 out=np.zeros(k), where=cm.sum(axis=1) > 0)
    per_class_prec = np.divide(np.diag(cm), cm.sum(axis=0),
                               out=np.zeros(k), where=cm.sum(axis=0) > 0)
    balanced_acc = per_class_recall.mean()
    recall = per_class_recall.mean()
    precision = per_class_prec.mean()

    # Cohen's kappa
    pe = float((cm.sum(axis=0) * cm.sum(axis=1)).sum()) / max(total ** 2, 1)
    kappa = (acc - pe) / max(1 - pe, 1e-12)

    # macro one-vs-rest AUC via the Mann–Whitney rank statistic, with
    # midranks so tied logits contribute 1/2 instead of an order-of-
    # appearance bias
    aucs = []
    for c in range(k):
        pos = logits[y_true == c, c]
        neg = logits[y_true != c, c]
        if len(pos) == 0 or len(neg) == 0:
            continue
        ranks = _midranks(np.concatenate([pos, neg]))
        auc = (ranks[: len(pos)].sum() - len(pos) * (len(pos) + 1) / 2) \
            / (len(pos) * len(neg))
        aucs.append(auc)
    auc = float(np.mean(aucs)) if aucs else 0.5

    return {"balanced_accuracy": float(balanced_acc), "accuracy": float(acc),
            "recall": float(recall), "kappa": float(kappa),
            "precision": float(precision), "auc": auc}
