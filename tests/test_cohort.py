"""Cohort engine: lifecycle, determinism, landmark quality, warm starts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cohort import (CohortConfig, CohortEngine, select_landmarks,
                          subspace_topk)
from repro.core import spectral_cluster
from repro.core.selection import DQREScSelection, RoundState

KEY = jax.random.PRNGKey(0)


def blobs(n=400, k=4, sep=8.0, d=8, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * sep
    labels = rng.integers(0, k, n)
    x = (centers[labels] + rng.normal(size=(n, d))).astype(np.float32)
    return x, labels


def skewed_blobs(seed=0, d=8, sep=10.0):
    """Non-IID fixture: a head cluster with 75 % of the clients + 5 tails."""
    rng = np.random.default_rng(seed)
    sizes = [450, 30, 30, 30, 30, 30]
    centers = rng.normal(size=(len(sizes), d)) * sep
    labels = np.repeat(np.arange(len(sizes)), sizes)
    x = (centers[labels]
         + rng.normal(size=(len(labels), d))).astype(np.float32)
    return x, labels


def purity(assign, labels):
    return sum(np.bincount(labels[assign == c]).max()
               for c in np.unique(assign)) / len(labels)


def test_engine_solves_at_highest_matmul_precision(monkeypatch):
    """A TPU's default f32 matmul is one bf16 pass; the whole solve
    (landmarks, Nyström, k-means) must trace at full precision."""
    seen = []
    solve = CohortEngine._prepare_f32

    def spy(self, *a, **kw):
        seen.append(jax.config.jax_default_matmul_precision)
        return solve(self, *a, **kw)

    monkeypatch.setattr(CohortEngine, "_prepare_f32", spy)
    x, _ = blobs(n=300)
    CohortEngine(CohortConfig(num_clusters=4), seed=0).select(x)
    assert seen == ["highest"]


def same_partition(a, b):
    """Label-permutation-invariant equality of two clusterings."""
    pa = a[:, None] == a[None, :]
    pb = b[:, None] == b[None, :]
    return bool(np.all(pa == pb))


# -- lifecycle ----------------------------------------------------------
def test_engine_dense_clusters_blobs():
    x, labels = blobs()
    res = CohortEngine(CohortConfig(num_clusters=4), seed=0).select(x)
    assert res.method == "dense" and res.source == "cold"
    assert purity(res.assign, labels) >= 0.95
    assert res.embedding.shape == (len(x), 4)


def test_engine_auto_method_resolution():
    small, _ = blobs(n=128)
    big, _ = blobs(n=2100)
    eng = CohortEngine(CohortConfig(num_clusters=4), seed=0)
    assert eng.select(small).method == "dense"
    # above the dense cutoff: always the jitted mesh path (1-way mesh on
    # a single device)
    assert eng.select(big).method == "sharded"


def test_engine_exact_cache_hit():
    x, _ = blobs()
    eng = CohortEngine(CohortConfig(num_clusters=4), seed=0)
    r1 = eng.select(x)
    r2 = eng.select(x)
    assert r2.source == "cache"
    assert np.array_equal(r1.assign, r2.assign)
    assert eng.stats["solves"] == 1 and eng.stats["cache_hits"] == 1


def test_engine_auto_k_caps_clusters():
    x, _ = blobs(k=2, sep=12.0)
    eng = CohortEngine(CohortConfig(num_clusters=6, auto_k=True), seed=0)
    res = eng.select(x)
    assert 2 <= res.k <= 6
    assert res.embedding.shape[1] == res.k
    assert res.assign.max() < res.k


def test_engine_rejects_bad_knobs():
    with pytest.raises(ValueError, match="method"):
        CohortConfig(method="magic")
    with pytest.raises(ValueError, match="strategy"):
        CohortConfig(landmarks="psychic")
    with pytest.raises(ValueError, match="solver"):
        CohortConfig(solver="cg")
    with pytest.raises(ValueError, match="strategy"):
        select_landmarks(KEY, jnp.zeros((8, 2)), 4, "psychic")


# -- determinism (satellite: explicit PRNG threading) -------------------
def test_engine_cold_solve_bit_identical_regardless_of_history():
    """Regression: PR 1 derived landmark seeds from a mutating key
    stream, so re-clustering the same embeddings after any other solve
    gave a different cohort.  Cold solves must be pure in (seed, embeds)."""
    x, _ = blobs(seed=0)
    y, _ = blobs(seed=7, sep=3.0)
    cfg = CohortConfig(num_clusters=4, method="nystrom", num_landmarks=64,
                       warm_start=False)
    eng = CohortEngine(cfg, seed=0)
    a1 = eng.select(x).assign.copy()
    eng.select(y)                               # unrelated solve between
    a2 = eng.select(x).assign
    assert np.array_equal(a1, a2)
    # and across engine instances with the same seed
    a3 = CohortEngine(cfg, seed=0).select(x).assign
    assert np.array_equal(a1, a3)


def test_spectral_cluster_nystrom_explicit_landmark_key():
    x = jnp.asarray(blobs(n=160)[0])
    lm = jax.random.PRNGKey(42)
    a1, y1, _ = spectral_cluster(KEY, x, 4, method="nystrom",
                                 num_landmarks=32, landmark_key=lm)
    a2, y2, _ = spectral_cluster(KEY, x, 4, method="nystrom",
                                 num_landmarks=32, landmark_key=lm)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    with pytest.raises(ValueError, match="landmark_key"):
        spectral_cluster(KEY, x, 4, landmark_key=lm)


def test_dqre_sc_policy_select_deterministic():
    x, _ = blobs(n=64, k=2)
    state = RoundState(0, x, np.zeros(8, np.float32), 0.1)
    sels = [DQREScSelection(64, 8, 8, seed=3, num_clusters=4,
                            approx_method="nystrom",
                            num_landmarks=16).select(state)
            for _ in range(2)]
    np.testing.assert_array_equal(sels[0], sels[1])


# -- landmark quality (acceptance: >= uniform purity on skewed data) ----
def test_landmark_strategies_beat_uniform_on_skewed_fixture():
    seeds = range(5)

    def mean_purity(strategy):
        ps = []
        for seed in seeds:
            x, labels = skewed_blobs(seed=seed)
            eng = CohortEngine(
                CohortConfig(num_clusters=6, method="nystrom",
                             num_landmarks=18, landmarks=strategy,
                             warm_start=False), seed=seed)
            ps.append(purity(eng.select(x).assign, labels))
        return float(np.mean(ps))

    uni = mean_purity("uniform")
    assert mean_purity("kmeans++") >= uni
    assert mean_purity("leverage") >= uni


def test_landmark_strategies_return_valid_unique_indices():
    x = jnp.asarray(skewed_blobs()[0])
    for strategy in ("uniform", "kmeans++", "leverage"):
        idx = np.asarray(select_landmarks(KEY, x, 24, strategy))
        assert idx.shape == (24,)
        assert len(np.unique(idx)) == 24
        assert idx.min() >= 0 and idx.max() < len(x)
        idx2 = np.asarray(select_landmarks(KEY, x, 24, strategy))
        np.testing.assert_array_equal(idx, idx2)    # pure in the key


# -- blocked eigensolver ------------------------------------------------
def test_subspace_topk_matches_eigh():
    rng = np.random.default_rng(0)
    b = rng.normal(size=(96, 96)).astype(np.float32)
    w = jnp.asarray(b @ b.T / 96)
    ref = np.linalg.eigh(np.asarray(w))
    evals, evecs = subspace_topk(w, 5, iters=80, key=KEY, block_rows=32)
    np.testing.assert_allclose(np.asarray(evals), ref[0][::-1][:5],
                               rtol=1e-3, atol=1e-4)
    # eigenvectors match up to sign: compare projectors
    p_ref = ref[1][:, ::-1][:, :5] @ ref[1][:, ::-1][:, :5].T
    v = np.asarray(evecs)
    np.testing.assert_allclose(v @ v.T, p_ref, atol=1e-2)


def test_subspace_topk_warm_start_converges_fast():
    rng = np.random.default_rng(1)
    b = rng.normal(size=(64, 64)).astype(np.float32)
    w = jnp.asarray(b @ b.T / 64)
    _, q = subspace_topk(w, 4, iters=80, key=KEY)
    # perturb the operator slightly, re-enter from the converged basis
    w2 = w + 1e-3 * jnp.asarray(np.diag(rng.normal(size=64))
                                .astype(np.float32))
    w2 = 0.5 * (w2 + w2.T)
    warm_evals, _ = subspace_topk(w2, 4, iters=3, q0=q)
    ref = np.linalg.eigh(np.asarray(w2))[0][::-1][:4]
    np.testing.assert_allclose(np.asarray(warm_evals), ref, rtol=1e-3,
                               atol=1e-4)


# -- incremental re-clustering (warm starts) ----------------------------
def _warm_cfg(**kw):
    base = dict(num_clusters=4, method="nystrom", num_landmarks=64,
                solver="subspace", drift_threshold=0.1)
    base.update(kw)
    return CohortConfig(**base)


def test_warm_start_equals_cold_start_after_convergence():
    """A drift-gated warm solve must reproduce the cold solve on the
    same (slightly drifted) embeddings: same partition, same spectrum."""
    x, _ = blobs()
    rng = np.random.default_rng(5)
    x2 = x + 0.01 * rng.normal(size=x.shape).astype(np.float32)

    warm_eng = CohortEngine(_warm_cfg(), seed=0)
    warm_eng.select(x)                                  # converge cold
    r_warm = warm_eng.select(x2)
    assert r_warm.source == "warm"
    assert warm_eng.stats["warm_starts"] == 1

    r_cold = CohortEngine(_warm_cfg(), seed=0).select(x2)
    assert r_cold.source == "cold"
    assert same_partition(r_warm.assign, r_cold.assign)
    np.testing.assert_allclose(r_warm.evals, r_cold.evals, atol=1e-2)


def test_explicit_key_bypasses_fingerprint_cache():
    """select(x, key=K) asks for a solve under K, not a cached replay."""
    x, _ = blobs()
    eng = CohortEngine(CohortConfig(num_clusters=4, method="nystrom",
                                    num_landmarks=64, warm_start=False),
                       seed=0)
    eng.select(x)
    r2 = eng.select(x, key=jax.random.PRNGKey(123))
    assert r2.source == "cold"                      # not "cache"
    # the probe really solved, but it is serving-invisible: persistent
    # counters (solves / cold_starts) only track the default stream
    assert eng.stats["solves"] == 1 and eng.stats["cache_hits"] == 0
    assert eng.stats["probes"] == 1
    assert eng.stats["cold_starts"] == 1


def test_explicit_key_probe_leaves_engine_state_untouched():
    """A one-off keyed probe must not poison the default stream's cache
    or warm-start state: the next default select must equal a fresh
    engine's result, and probe state must not be persisted."""
    x, _ = blobs()
    cfg = CohortConfig(num_clusters=4, method="nystrom", num_landmarks=64,
                       warm_start=False)
    a_ref = CohortEngine(cfg, seed=0).select(x).assign
    eng = CohortEngine(cfg, seed=0)
    eng.select(x, key=jax.random.PRNGKey(999))      # probe first
    assert eng.state.fingerprint is None            # nothing persisted
    np.testing.assert_array_equal(eng.select(x).assign, a_ref)


def test_explicit_key_probe_never_warm_starts():
    """Probes must be fully determined by their key: even with warm
    state available, a keyed select re-samples landmarks under that key
    instead of silently replaying the persisted ones."""
    x, _ = blobs()
    eng = CohortEngine(_warm_cfg(), seed=0)
    eng.select(x)                                   # persist warm state
    r1 = eng.select(x, key=jax.random.PRNGKey(1))
    r2 = eng.select(x, key=jax.random.PRNGKey(2))
    assert r1.source == "cold" and r2.source == "cold"
    assert not np.array_equal(r1.embedding, r2.embedding)


def test_gap_history_is_bounded_in_long_running_engines():
    """The autotuner only reads the last two eigengaps; a server calling
    select for months must not grow the history unboundedly."""
    from repro.cohort.engine import _GAP_HIST_MAX

    eng = CohortEngine(CohortConfig(num_clusters=4, num_landmarks="auto"),
                       seed=0)
    evals = np.linspace(0.0, 1.0, 6)
    for _ in range(10 * _GAP_HIST_MAX):
        eng._update_auto_m(n=1000, k=4, drift=0.01, evals=evals)
    assert len(eng._gap_hist) == _GAP_HIST_MAX


def test_cache_hit_returns_copies_not_aliases():
    x, _ = blobs()
    eng = CohortEngine(CohortConfig(num_clusters=4), seed=0)
    eng.select(x)
    r_cached = eng.select(x)
    assert r_cached.source == "cache"
    assert r_cached.assign is not eng.state.result.assign
    r_cached.assign[:] = 0                          # caller mutates copy
    assert len(np.unique(eng.select(x).assign)) > 1


def test_policy_rejects_mismatched_cohort_config():
    with pytest.raises(ValueError, match="num_clusters"):
        DQREScSelection(64, 8, 8, num_clusters=4,
                        cohort_config=CohortConfig(num_clusters=8))
    # overlapping constructor args must not be silently discarded
    with pytest.raises(ValueError, match="cohort_config"):
        DQREScSelection(64, 8, 8, num_clusters=4,
                        approx_method="nystrom", num_landmarks=16,
                        cohort_config=CohortConfig(num_clusters=4))


def test_auto_k_subspace_sees_full_eigengap_window():
    """Regression: subspace solvers returned only k eigenvalues, so the
    eigengap never saw the lambda_k/lambda_{k+1} gap and auto_k was
    silently capped at k-1.  The engine now solves k+1 wide under
    auto_k, so both solvers see the same gap window and must agree."""
    x, _ = blobs(n=240, k=4, sep=12.0)

    def run(solver):
        eng = CohortEngine(
            CohortConfig(num_clusters=4, method="nystrom",
                         num_landmarks=64, solver=solver, auto_k=True,
                         warm_start=False), seed=0)
        return eng.select(x)

    r_sub, r_eigh = run("subspace"), run("eigh")
    assert len(r_sub.evals) == 5          # k+1, not k
    assert r_sub.k == r_eigh.k            # same eigengap decision
    assert r_sub.embedding.shape[1] == r_sub.k


def test_cumulative_drift_eventually_forces_cold_refresh():
    """Drift is measured against the last COLD baseline, so steady
    sub-threshold per-round drift accumulates and must trigger a
    landmark/bandwidth refresh instead of warm-starting forever."""
    x, _ = blobs()
    eng = CohortEngine(_warm_cfg(), seed=0)
    eng.select(x)
    rng = np.random.default_rng(11)
    step = rng.normal(size=x.shape).astype(np.float32)
    step *= 0.02 * np.linalg.norm(x) / np.linalg.norm(step)
    sources = []
    for t in range(1, 30):
        sources.append(eng.select(x + t * step).source)
        if sources[-1] == "cold":
            break
    assert "warm" in sources                        # warm path exercised
    assert sources[-1] == "cold"                    # ...but not forever


def test_large_drift_forces_cold_start():
    x, _ = blobs(seed=0)
    y, _ = blobs(seed=9, sep=3.0)
    eng = CohortEngine(_warm_cfg(), seed=0)
    eng.select(x)
    res = eng.select(y)
    assert res.source == "cold" and res.drift > 0.1
    assert eng.stats["warm_starts"] == 0


def test_warm_start_disabled_by_config():
    x, _ = blobs()
    rng = np.random.default_rng(5)
    x2 = x + 0.001 * rng.normal(size=x.shape).astype(np.float32)
    eng = CohortEngine(_warm_cfg(warm_start=False), seed=0)
    eng.select(x)
    assert eng.select(x2).source == "cold"


def test_engine_reset_drops_state():
    x, _ = blobs()
    eng = CohortEngine(_warm_cfg(), seed=0)
    eng.select(x)
    eng.reset()
    assert eng.state.fingerprint is None
    assert eng.select(x).source == "cold"      # no cache hit after reset


# -- policy + runner integration ---------------------------------------
def test_policy_cluster_computes_tracks_engine_solves():
    x, _ = blobs(n=64, k=2)
    pol = DQREScSelection(64, 8, 8, seed=0, num_clusters=4)
    state = RoundState(0, x, np.zeros(8, np.float32), 0.1)
    pol.select(state)
    assert pol.cluster_computes == 1
    pol.select(state)
    assert pol.cluster_computes == 1           # engine cache hit
    assert pol.engine.stats["cache_hits"] == 1


def test_runner_config_threads_cohort_knobs():
    from repro.fed import RunnerConfig
    from repro.fed.rounds import FederatedRunner
    cfg = RunnerConfig(num_clients=12, clients_per_round=4,
                       train_size=256, eval_size=64, policy="dqre_sc",
                       num_clusters=3, approx_method="nystrom",
                       num_landmarks=8, landmarks="kmeans++",
                       warm_start=False)
    runner = FederatedRunner(cfg)
    eng_cfg = runner.policy.engine.config
    assert eng_cfg.method == "nystrom"
    assert eng_cfg.landmarks == "kmeans++"
    assert eng_cfg.num_landmarks == 8
    assert eng_cfg.warm_start is False


# -- landmark-count autotuning (num_landmarks="auto") -------------------
def test_config_rejects_bogus_num_landmarks():
    with pytest.raises(ValueError, match="num_landmarks"):
        CohortConfig(num_landmarks="bogus")
    with pytest.raises(ValueError, match="num_landmarks"):
        CohortConfig(num_landmarks=-4)


def test_auto_landmarks_keeps_base_on_separated_blobs():
    """Strong eigengap -> the static default max(8k, 64) is enough; the
    autotuner must not inflate m (and the result stays valid)."""
    from repro.core.spectral import default_num_landmarks
    x, labels = blobs()
    eng = CohortEngine(CohortConfig(num_clusters=4, method="nystrom",
                                    num_landmarks="auto"), seed=0)
    res = eng.select(x)
    assert res.assign.shape == (len(x),)
    assert purity(res.assign, labels) >= 0.9
    assert eng.stats["auto_m"] == default_num_landmarks(len(x), 4)
    # the widened (k+1) solve is an internal detail: the published
    # embedding keeps the configured k columns
    assert res.embedding.shape[1] == 4


def test_auto_landmarks_grows_on_weak_eigengap():
    """Structureless embeddings show no k-cluster gap -> the autotuner
    doubles m (bounded) on consecutive cold solves."""
    from repro.core.spectral import default_num_landmarks
    rng = np.random.default_rng(0)
    base = default_num_landmarks(400, 4)
    eng = CohortEngine(CohortConfig(num_clusters=4, method="nystrom",
                                    num_landmarks="auto",
                                    warm_start=False), seed=0)
    for _ in range(2):
        eng.select(rng.normal(size=(400, 8)).astype(np.float32))
    assert eng.stats["auto_m"] > base
    assert eng.stats["auto_m"] <= 8 * base


def test_auto_landmarks_stable_under_warm_starts():
    """Warm solves must not retune m (the warm-start check requires the
    persisted landmark set to keep its size)."""
    x, _ = blobs()
    rng = np.random.default_rng(3)
    eng = CohortEngine(CohortConfig(num_clusters=4, method="nystrom",
                                    num_landmarks="auto",
                                    drift_threshold=0.1), seed=0)
    eng.select(x)
    m0 = eng.stats["auto_m"]
    r = eng.select(x + 0.01 * rng.normal(size=x.shape).astype(np.float32))
    assert r.source == "warm"
    assert eng.stats["auto_m"] == m0


def test_auto_landmarks_bases_m_on_configured_k():
    """Regression: the widened (k+1) solve must base m on the configured
    k, not the solve width — at num_clusters=9 the k+1 base made the
    first solve use 80 landmarks while auto_m recorded 72, so the
    warm-start size check could never match and every solve ran cold."""
    from repro.core.spectral import default_num_landmarks
    x, _ = blobs(n=300, k=8)
    rng = np.random.default_rng(5)
    eng = CohortEngine(CohortConfig(num_clusters=9, method="nystrom",
                                    num_landmarks="auto",
                                    drift_threshold=0.1), seed=0)
    eng.select(x)
    assert len(eng.state.landmark_idx) == default_num_landmarks(300, 9)
    # invariant: auto_m is always the m the NEXT solve actually uses,
    # even while the weak-gap escalation is doubling it
    for _ in range(3):
        m_next = eng.stats["auto_m"]
        x = x + 0.005 * rng.normal(size=x.shape).astype(np.float32)
        eng.select(x)
        assert len(eng.state.landmark_idx) == m_next
    # once m stops moving (capped or strong gap), warm starts resume
    if eng.stats["auto_m"] == len(eng.state.landmark_idx):
        r = eng.select(
            x + 0.005 * rng.normal(size=x.shape).astype(np.float32))
        assert r.source == "warm"
