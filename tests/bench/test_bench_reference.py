"""The plain reference and the comparison that decides ``correct``, on
the CPU at a tiny size: the program's fused float32 solve passes it, its
bfloat16-tile path (the control) fails it, the reference at
``Precision.HIGH`` reads ten times the program's spectrum gap, and
products in one bfloat16 pass fail it."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import compare, reference, spec, traffic  # noqa: E402

N, D, K, M = 4096, 8, 4, 64
SEEDS = [3, 2**32 + 5, 977]


def limits():
    return spec.load_cell("xdevice-1m.churn").cell["limits"]


def population(seed):
    return traffic.planted_table(N, K, D, seed, cluster_zipf=1.1,
                                 center_scale=6.0)


def table(seed):
    return population(seed).table


def program_solve(x, affinity_dtype="f32"):
    from repro.cohort import CohortConfig, CohortEngine
    from repro.launch.mesh import make_cohort_mesh
    engine = CohortEngine(CohortConfig(
        num_clusters=K, num_landmarks=M, use_pallas=True,
        affinity_dtype=affinity_dtype, method="sharded"),
        seed=11, mesh=make_cohort_mesh(1))
    res = engine.select(x)
    return res, engine.state.landmark_idx, engine.state.gamma


def failing(numbers):
    lim = limits()
    return [n for n in compare.SOLVE_NUMBERS
            if n in numbers and numbers[n] > lim[n]]


@pytest.mark.parametrize("seed", SEEDS)
def test_fused_f32_solve_passes(seed):
    pop = population(seed)
    res, idx, gamma = program_solve(pop.table)
    numbers = compare.compare_solve(res, pop.table, pop.labels, idx, gamma,
                                    K)
    assert failing(numbers) == [], numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_tile_path_fails(seed):
    """The control: the program's own bfloat16 affinity tiles.  Each
    embedding row then errs by ~10⁻³ against the float64 affinity, where
    float32 tiles err by ~10⁻⁶ here."""
    pop = population(seed)
    res, idx, gamma = program_solve(pop.table, "bf16")
    numbers = compare.compare_solve(res, pop.table, pop.labels, idx, gamma,
                                    K)
    assert "embedding_gap" in failing(numbers), numbers


def test_embedding_gap_ignores_a_change_of_basis():
    """Any (m, k) map of the affinity rows, normalized, reads ~0; moving
    one row in ten to another client reads far above the limit."""
    rng = np.random.default_rng(0)
    c = np.exp(-rng.uniform(0.0, 4.0, (5000, 16)))
    y = c @ rng.standard_normal((16, K))
    y /= np.linalg.norm(y, axis=1, keepdims=True)
    assert compare.embedding_gap(y, c, 1) < 1e-8
    rows = np.arange(0, len(y), 10)
    y[rows] = y[np.roll(rows, 1)]
    assert compare.embedding_gap(y, c, 1) > 10 * limits()["embedding_gap"]


def test_impurity_counts_clients_outside_the_majority():
    labels = np.array([0, 0, 0, 1, 1, 2])
    assert compare.impurity(np.array([0, 0, 0, 1, 1, 1]), labels) == (
        pytest.approx(1 / 6))
    assert compare.impurity(labels, labels) == 0.0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_at_high_precision_separates(seed):
    """The reference with three-pass products in the program's place
    reads ten times and more the program's own spectrum gap."""
    pop = population(seed)
    res, idx, gamma = program_solve(pop.table)
    sound = compare.compare_solve(res, pop.table, pop.labels, idx, gamma, K)
    control = compare.control_numbers(pop.table, idx, gamma, K)
    assert control["spectrum_gap"] >= 10 * sound["spectrum_gap"], (
        sound, control)


@pytest.mark.parametrize("seed", SEEDS)
def test_one_pass_bf16_products_fail(seed):
    """A solve that drops the engine's HIGHEST precision (one bfloat16
    pass per product, a TPU's default for f32) fails the limits."""
    x = table(seed)
    _, idx, gamma = program_solve(x)
    numbers = compare.control_numbers(x, idx, gamma, K, precision="default")
    assert failing(numbers) == ["spectrum_gap"], numbers


def test_reference_matches_float64_numpy():
    """The blocked device reference against a dense float64 Nyström."""
    x = table(1)[:3000]
    rng = np.random.default_rng(0)
    idx = np.sort(rng.choice(len(x), 32, replace=False))
    gamma = 1.0 / 300.0
    evals = reference.spectrum(x, idx, gamma)
    xd = x.astype(np.float64)
    z = xd[idx]
    c = np.exp(-gamma * ((xd[:, None] - z[None]) ** 2).sum(-1))
    w = np.exp(-gamma * ((z[:, None] - z[None]) ** 2).sum(-1))
    ew, uw = np.linalg.eigh(w)
    keep = ew > max(1e-6, 32 * np.finfo(np.float32).eps) * ew.max()
    w_is = (uw[:, keep] / np.sqrt(ew[keep])) @ uw[:, keep].T
    d_hat = c @ (w_is @ (w_is @ c.sum(0)))
    s = c / np.sqrt(d_hat)[:, None]
    lam = np.linalg.eigvalsh(w_is @ (s.T @ s) @ w_is)[::-1]
    np.testing.assert_allclose(evals[:K], 1.0 - lam[:K], atol=1e-5)


def test_kernel_name_table_names_the_fused_passes():
    groups = spec._load_json(os.path.join(spec.BENCH, "kernels.json"))
    ops = groups["groups"]["nystrom"]["ops"]
    assert groups["groups"]["nystrom"]["count_by"] in ops
