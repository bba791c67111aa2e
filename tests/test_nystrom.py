"""Approximate spectral selection: Nyström landmark path + subspace
eigensolver vs the dense Algorithm I oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (affinity_matrix, eigengap_k,
                        nystrom_spectral_embedding, spectral_cluster,
                        spectral_embedding)

KEY = jax.random.PRNGKey(0)


def blobs(n=160, k=2, sep=8.0, d=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * sep
    labels = np.repeat(np.arange(k), n // k)
    x = centers[labels] + rng.normal(size=(len(labels), d))
    return x.astype(np.float32), labels


def purity(assign, labels, k):
    total = sum(np.bincount(labels[assign == c]).max()
                for c in range(k) if (assign == c).any())
    return total / len(labels)


@pytest.mark.parametrize("k", [2, 4])
def test_nystrom_matches_dense_oracle_purity(k):
    """Acceptance: blob purity >= 0.95 with m = N/8 landmarks."""
    x, labels = blobs(n=160, k=k)
    assign, _, _ = spectral_cluster(KEY, jnp.asarray(x), k,
                                    method="nystrom",
                                    num_landmarks=len(x) // 8)
    assert purity(np.asarray(assign), labels, k) >= 0.95


def test_nystrom_embedding_rows_unit_norm_and_spectrum():
    x, _ = blobs()
    y, evals = nystrom_spectral_embedding(KEY, jnp.asarray(x), 2, 20)
    norms = np.linalg.norm(np.asarray(y), axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-4)
    evals = np.asarray(evals)
    # approximates the L_norm spectrum: near-zero head, bounded by 2
    assert evals[0] < 1e-3
    assert evals.max() <= 2.0 + 1e-4


def test_nystrom_eigengap_detects_two_clusters():
    x, _ = blobs(sep=12.0)
    _, evals = nystrom_spectral_embedding(KEY, jnp.asarray(x), 2, 32,
                                          gamma=0.5)
    assert int(eigengap_k(evals)) == 2


def test_nystrom_evals_close_to_dense():
    """Leading eigenvalues of the approximate L_norm track the exact ones."""
    x, _ = blobs(n=120)
    a = affinity_matrix(jnp.asarray(x), gamma=0.5)
    _, dense_evals = spectral_embedding(a, 2)
    _, nys_evals = nystrom_spectral_embedding(KEY, jnp.asarray(x), 2, 60,
                                              gamma=0.5)
    np.testing.assert_allclose(np.asarray(nys_evals[:2]),
                               np.asarray(dense_evals[:2]), atol=0.1)


def test_subspace_solver_matches_eigh():
    """Orthogonal iteration recovers the same smallest-k eigenpairs."""
    x, labels = blobs(n=120)
    a = affinity_matrix(jnp.asarray(x), gamma=0.5)
    y_exact, ev_exact = spectral_embedding(a, 2, solver="eigh")
    y_sub, ev_sub = spectral_embedding(a, 2, solver="subspace", iters=80)
    np.testing.assert_allclose(np.asarray(ev_sub),
                               np.asarray(ev_exact[:2]), atol=1e-3)
    # eigenvectors match up to sign/rotation: compare projectors
    p_exact = np.asarray(y_exact) @ np.asarray(y_exact).T
    p_sub = np.asarray(y_sub) @ np.asarray(y_sub).T
    np.testing.assert_allclose(p_sub, p_exact, atol=1e-2)


def test_subspace_clustering_separates_blobs():
    x, labels = blobs()
    assign, _, _ = spectral_cluster(KEY, jnp.asarray(x), 2, solver="subspace")
    assert purity(np.asarray(assign), labels, 2) >= 0.95


def test_nystrom_pallas_path_agrees():
    """The two paths may differ by a rotation inside the degenerate
    leading eigenspace, so compare the rotation-free projector Y·Yᵀ."""
    x, _ = blobs(n=96)
    y_jnp, _ = nystrom_spectral_embedding(KEY, jnp.asarray(x), 2, 24,
                                          gamma=0.5, use_pallas=False)
    y_pal, _ = nystrom_spectral_embedding(KEY, jnp.asarray(x), 2, 24,
                                          gamma=0.5, use_pallas=True)
    y_jnp, y_pal = np.asarray(y_jnp), np.asarray(y_pal)
    np.testing.assert_allclose(y_pal @ y_pal.T, y_jnp @ y_jnp.T, atol=1e-3)


def test_nystrom_all_landmarks_degenerates_gracefully():
    """m = n (every point a landmark) must still cluster correctly."""
    x, labels = blobs(n=64)
    assign, _, _ = spectral_cluster(KEY, jnp.asarray(x), 2,
                                    method="nystrom", num_landmarks=64)
    assert purity(np.asarray(assign), labels, 2) >= 0.95


def test_incompatible_knob_combinations_rejected():
    """solver is a dense-path knob, num_landmarks a nystrom-path knob;
    silently ignoring either would let callers benchmark the wrong
    algorithm."""
    x = jnp.asarray(blobs(n=32)[0])
    with pytest.raises(ValueError, match="num_landmarks"):
        spectral_cluster(KEY, x, 2, method="dense", num_landmarks=8)
    with pytest.raises(ValueError, match="solver"):
        spectral_cluster(KEY, x, 2, method="nystrom", solver="subspace")


def test_dqre_sc_auto_k_nystrom_avoids_dense_path(monkeypatch):
    """auto_k with approx_method='nystrom' must estimate the eigengap
    from the landmark spectrum — building the dense affinity would
    reintroduce the O(N²) ceiling."""
    import repro.core.spectral as S
    from repro.core.selection import DQREScSelection, RoundState

    def boom(*a, **kw):
        raise AssertionError("dense affinity built on the nystrom path")

    monkeypatch.setattr(S, "affinity_matrix", boom)
    x, _ = blobs(n=64, k=2)
    pol = DQREScSelection(64, 8, 4, seed=0, num_clusters=4, auto_k=True,
                          approx_method="nystrom", num_landmarks=16)
    sel = pol.select(RoundState(0, x, np.zeros(4, np.float32), 0.1))
    assert len(set(sel.tolist())) == 8


@pytest.mark.slow
def test_dqre_sc_select_100k_clients():
    """Acceptance: a 100k-client cohort selection completes (in seconds on
    CPU) via the Nyström path, where the dense path would OOM on the
    10¹⁰-entry affinity matrix."""
    from repro.core.selection import DQREScSelection, RoundState
    n, d = 100_000, 8
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(8, d)) * 6
    embeds = (centers[rng.integers(0, 8, n)]
              + rng.normal(size=(n, d))).astype(np.float32)
    pol = DQREScSelection(n, 64, d, seed=0, num_clusters=8,
                          approx_method="nystrom", num_landmarks=512)
    sel = pol.select(RoundState(0, embeds, np.zeros(d, np.float32), 0.1))
    assert len(sel) == 64
    assert len(set(sel.tolist())) == 64


@pytest.mark.parametrize("n", [7, 16, 50])
def test_blocked_gram_equals_full_product(n):
    """SᵀS summed over row slices (ragged tail, fewer rows than one
    slice, an exact multiple) equals the one-shot product."""
    from repro.cohort.nystrom import _gram
    s = jnp.asarray(np.random.default_rng(n).normal(size=(n, 5)),
                    jnp.float32)
    np.testing.assert_allclose(_gram(s, block=16), s.T @ s,
                               rtol=1e-5, atol=1e-5)


def test_isqrt_drops_eigenvalues_below_the_f32_noise_floor():
    """W's eigenvalues under m·ε·λ_max are rounding noise and get no
    Λ^{-1/2} weight; those above keep it."""
    from repro.cohort.eigensolver import isqrt_from_eigs
    m = 64
    floor = m * np.finfo(np.float32).eps                # ~7.6e-6
    evals = jnp.asarray([1.0, 2 * floor, 0.5 * floor] + [1.0] * (m - 3),
                        jnp.float32)
    got = np.diag(np.asarray(isqrt_from_eigs(evals, jnp.eye(m))))
    np.testing.assert_allclose(got[:2], 1 / np.sqrt([1.0, 2 * floor]),
                               rtol=1e-5)
    assert got[2] == 0.0
