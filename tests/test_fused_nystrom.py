"""Streaming fused Nyström pipeline vs the composed jnp reference.

Three layers of guarantees:

* kernel-level — every fused pass (`nystrom_colsum/gram/extension`,
  `panel_matmul`, `quantized_cross_affinity`) matches its naive oracle
  in ``kernels/ref.py`` across shapes, row-panel sizes, and all three
  ``affinity_dtype`` tile precisions;
* pipeline-level — `nystrom_from_landmarks(fused=True)` agrees with the
  ``fused=False`` jnp composition on every ROTATION-INVARIANT quantity
  (spectrum, the y·yᵀ projector, cluster partitions).  Raw embeddings
  are deliberately not compared: well-separated clusters make the
  leading eigenspace degenerate, so the ~1e-7 tiled-accumulation
  differences rotate individual eigenvectors arbitrarily;
* system-level — quantized (bf16/int8) engine solves hold the purity
  floor on the skewed non-IID fixture, and the `use_pallas` toggle is
  thread-safe.

A hypothesis block (skipped without the 'dev' extra) fuzzes the
kernel-vs-oracle agreement over random shapes.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cohort.engine import CohortConfig, CohortEngine
from repro.cohort.eigensolver import _blocked_matmul, subspace_topk
from repro.cohort.nystrom import nystrom_from_landmarks
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)
DTYPES = ("f32", "bf16", "int8")


def blobs(n=509, k=4, sep=8.0, d=8, seed=0):
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
    assign = np.asarray(assign)
    return sum(np.bincount(labels[assign == c]).max()
               for c in np.unique(assign)) / len(labels)


def same_partition(a, b):
    a, b = np.asarray(a), np.asarray(b)
    pairs = {(int(x), int(y)) for x, y in zip(a, b)}
    return len(pairs) == len(set(a)) == len(set(b))


def _fixture(n=261, m=65, d=7, k=5, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    mask = jnp.asarray((rng.random(n) > 0.1).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(m,)) ** 2 + 0.1, jnp.float32)
    wis = jnp.asarray(rng.normal(size=(m, m)), jnp.float32)
    proj = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    return x, z, 0.37, mask, u, wis, proj


# -- kernel vs oracle -------------------------------------------------------

@pytest.mark.parametrize("affinity_dtype", DTYPES)
@pytest.mark.parametrize("block_m", [32, 128, 1024])
def test_fused_passes_match_oracles(affinity_dtype, block_m):
    x, z, gamma, mask, u, wis, proj = _fixture()
    kw = dict(affinity_dtype=affinity_dtype, block_m=block_m)
    np.testing.assert_allclose(
        ops.nystrom_colsum(x, z, gamma, mask, **kw),
        ref.nystrom_colsum_ref(x, z, gamma, mask,
                               affinity_dtype=affinity_dtype),
        rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(
        ops.nystrom_gram(x, z, gamma, u, wis, mask, **kw),
        ref.nystrom_gram_ref(x, z, gamma, u, wis, mask,
                             affinity_dtype=affinity_dtype),
        rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(
        ops.nystrom_extension(x, z, gamma, u, proj, mask, **kw),
        ref.nystrom_extension_ref(x, z, gamma, u, proj, mask,
                                  affinity_dtype=affinity_dtype),
        rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        ops.quantized_cross_affinity(x, z, gamma, **kw),
        ref.quantized_cross_affinity_ref(x, z, gamma,
                                         affinity_dtype=affinity_dtype),
        rtol=2e-5, atol=2e-4)


def test_gram_column_blocks_match_oracle():
    """Past the VMEM-sized column block the Gram accumulates in several
    (m, bn) blocks over zero-padded landmarks; the padding must not leak
    into the degree scaling or the sliced-off result."""
    from repro.kernels import nystrom_pallas
    m = 1500
    assert nystrom_pallas._gram_col_block(m) < m
    x, _, gamma, mask, *_ = _fixture(n=40, d=8)
    rng = np.random.default_rng(5)
    z = jnp.asarray(rng.normal(size=(m, 8)), jnp.float32)
    u = jnp.asarray(rng.random(m) + 0.1, jnp.float32)
    wis = jnp.asarray(rng.normal(size=(m, m)) / m, jnp.float32)
    np.testing.assert_allclose(
        ops.nystrom_gram(x, z, gamma, u, wis, mask),
        ref.nystrom_gram_ref(x, z, gamma, u, wis, mask),
        rtol=2e-4, atol=2e-5)


def test_unmasked_equals_ones_mask():
    x, z, gamma, _, u, wis, proj = _fixture()
    ones = jnp.ones((x.shape[0],), jnp.float32)
    np.testing.assert_array_equal(ops.nystrom_colsum(x, z, gamma),
                                  ops.nystrom_colsum(x, z, gamma, ones))


def test_f32_quantized_cross_is_bitwise_legacy_kernel():
    """affinity_dtype="f32" must reproduce the PR-1 cross kernel exactly
    — the fused path's W block stays backend-consistent with it."""
    x, z, gamma, *_ = _fixture()
    a = ops.quantized_cross_affinity(x, z, gamma, affinity_dtype="f32")
    b = ops.rbf_cross_affinity(x, z, gamma)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_extension_rows_unit_norm_masked_rows_zero():
    x, z, gamma, mask, u, _, proj = _fixture()
    v = np.asarray(ops.nystrom_extension(x, z, gamma, u, proj, mask))
    norms = np.linalg.norm(v, axis=1)
    live = np.asarray(mask) > 0
    np.testing.assert_allclose(norms[live], 1.0, atol=1e-5)
    assert np.abs(v[~live]).max() == 0.0


def test_masked_rows_equal_truncated_input():
    """Zero-masked trailing rows must reproduce the solve on the prefix —
    the invariant the shard_map global padding relies on."""
    x, z, gamma, _, u, wis, proj = _fixture(n=300)
    n_live = 211
    mask = (jnp.arange(300) < n_live).astype(jnp.float32)
    np.testing.assert_allclose(
        ops.nystrom_colsum(x, z, gamma, mask, block_m=64),
        ops.nystrom_colsum(x[:n_live], z, gamma, block_m=64),
        rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(
        ops.nystrom_gram(x, z, gamma, u, wis, mask, block_m=64),
        ops.nystrom_gram(x[:n_live], z, gamma, u, wis, block_m=64),
        rtol=1e-4, atol=1e-4)


# -- eigensolver panel matmul ----------------------------------------------

def test_panel_matmul_bitwise_blocked_matmul():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(130, 130)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(130, 9)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.panel_matmul(w, q, block_rows=32)),
        np.asarray(_blocked_matmul(w, q, 32)))
    np.testing.assert_allclose(ops.panel_matmul(w, q, block_rows=32),
                               ref.panel_matmul_ref(w, q),
                               rtol=1e-5, atol=1e-5)


def test_subspace_topk_pallas_route_agrees():
    rng = np.random.default_rng(3)
    a = rng.normal(size=(96, 96)).astype(np.float32)
    w = jnp.asarray(a @ a.T)
    e0, v0 = subspace_topk(w, 6, iters=40, block_rows=32, use_pallas=False)
    e1, v1 = subspace_topk(w, 6, iters=40, block_rows=32, use_pallas=True)
    np.testing.assert_allclose(e0, e1, rtol=1e-4, atol=1e-4)
    # compare subspaces via projectors (eigenvector signs are arbitrary)
    np.testing.assert_allclose(v0 @ v0.T, v1 @ v1.T, atol=1e-3)


# -- fused pipeline vs composed jnp reference ------------------------------

@pytest.mark.parametrize("affinity_dtype", DTYPES)
def test_fused_pipeline_matches_composed_reference(affinity_dtype):
    """Rotation-invariant agreement: spectrum tight for f32, within the
    quantization budget for bf16/int8; projector + partition for all."""
    from repro.core.kmeans import kmeans

    x, labels = blobs(n=700)
    x = jnp.asarray(x)
    k = 4
    idx = jnp.asarray(
        np.random.default_rng(1).choice(700, 96, replace=False))
    gamma = 0.05
    y0, e0, _, _ = nystrom_from_landmarks(x, idx, k, gamma)
    y1, e1, _, _ = nystrom_from_landmarks(x, idx, k, gamma, fused=True,
                                          affinity_dtype=affinity_dtype)
    tol = 1e-3 if affinity_dtype == "f32" else 2e-2
    np.testing.assert_allclose(e0[:k + 1], e1[:k + 1], atol=tol)
    np.testing.assert_allclose(np.asarray(y0 @ y0.T),
                               np.asarray(y1 @ y1.T), atol=5e-2)
    a0, _ = kmeans(KEY, y0, k)
    a1, _ = kmeans(KEY, y1, k)
    assert same_partition(a0, a1)
    assert purity(a1, labels) >= purity(a0, labels) - 1e-3


def test_fused_subspace_solver_pipeline():
    """The fused path composes with the blocked subspace eigensolver
    (warm-startable route) — partition must match the composed path."""
    from repro.core.kmeans import kmeans

    x, labels = blobs(n=600)
    x = jnp.asarray(x)
    k = 4
    idx = jnp.asarray(
        np.random.default_rng(4).choice(600, 64, replace=False))
    gamma = 0.05
    kw = dict(w_solver="subspace", w_rank=32, mm_solver="subspace",
              iters=40, key=KEY, block_rows=32)
    y0, e0, _, _ = nystrom_from_landmarks(x, idx, k, gamma, **kw)
    y1, e1, _, _ = nystrom_from_landmarks(x, idx, k, gamma, fused=True,
                                          **kw)
    np.testing.assert_allclose(e0[:k], e1[:k], atol=1e-3)
    a0, _ = kmeans(KEY, y0, k)
    a1, _ = kmeans(KEY, y1, k)
    assert same_partition(a0, a1)
    assert purity(a1, labels) >= 0.95


# -- engine-level quantized purity floor (skewed non-IID fixture) ----------

@pytest.mark.parametrize("affinity_dtype", DTYPES)
@pytest.mark.parametrize("method", ["nystrom", "sharded"])
def test_quantized_engine_purity_floor_on_skewed_fixture(method,
                                                         affinity_dtype):
    """The acceptance gate: quantized tiles must not cost clustering
    quality on the non-IID population the paper targets."""
    x, labels = skewed_blobs()
    eng = CohortEngine(CohortConfig(num_clusters=6, method=method,
                                    num_landmarks=96, use_pallas=True,
                                    affinity_dtype=affinity_dtype),
                       seed=0)
    res = eng.select(x)
    assert purity(res.assign, labels) >= 0.95


def test_engine_refuses_landmarks_past_kernel_limit():
    """A landmark count the fused kernels cannot compile for is refused
    with the limit named — by the config when pinned, by the engine
    before tracing when the default max(8k, 64) gets there — and never
    rerouted to the jnp path."""
    from repro.kernels.nystrom_pallas import MAX_LANDMARKS
    too_many = MAX_LANDMARKS + 1
    with pytest.raises(ValueError, match=str(MAX_LANDMARKS)):
        CohortConfig(num_landmarks=too_many, use_pallas=True)
    CohortConfig(num_landmarks=too_many)            # jnp path: no limit
    k = MAX_LANDMARKS // 8 + 1                       # default m = 8k
    eng = CohortEngine(CohortConfig(num_clusters=k, use_pallas=True,
                                    method="nystrom"), seed=0)
    x = np.zeros((8 * k + 1, 2), np.float32)
    with pytest.raises(ValueError, match=str(MAX_LANDMARKS)):
        eng.select(x)
    assert eng.stats["solves"] == 0


def test_engine_affinity_dtype_validation():
    with pytest.raises(ValueError, match="affinity_dtype"):
        CohortConfig(affinity_dtype="fp8", use_pallas=True)
    with pytest.raises(ValueError, match="requires use_pallas"):
        CohortConfig(affinity_dtype="int8")


# -- thread-safe substrate toggle ------------------------------------------

def test_use_pallas_toggle_thread_safety_and_scoping():
    """Hammer the toggle from many threads; the flag must always be a
    bool (no torn state) and every scope must restore what it saw."""
    base = ops.use_pallas()
    errors = []

    def hammer(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(200):
                if rng.random() < 0.5:
                    with ops.use_pallas_scoped(bool(rng.random() < 0.5)):
                        assert ops.use_pallas() in (True, False)
                else:
                    ops.set_use_pallas(bool(rng.random() < 0.5))
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    ops.set_use_pallas(base)
    with ops.use_pallas_scoped(not base):
        assert ops.use_pallas() is (not base)
    assert ops.use_pallas() is base


# -- hypothesis fuzzing (needs the 'dev' extra) ----------------------------
# Conditionally defined (not importorskip): this module's deterministic
# coverage must still run where hypothesis is absent.

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised in the dev env
    @pytest.mark.skip(
        reason="property tests need the 'dev' extra (pip install -e .[dev])")
    def test_fuzz_fused_passes_match_oracles():
        pass
else:
    _settings = settings(max_examples=15, deadline=None)

    @_settings
    @given(st.integers(3, 80), st.integers(2, 24), st.integers(1, 6),
           st.sampled_from([8, 32, 128]), st.sampled_from(DTYPES),
           st.integers(0, 2 ** 31 - 1))
    def test_fuzz_fused_passes_match_oracles(n, m, d, block_m,
                                             affinity_dtype, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        z = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(m,)) ** 2 + 0.1, jnp.float32)
        wis = jnp.asarray(rng.normal(size=(m, m)), jnp.float32)
        proj = jnp.asarray(rng.normal(size=(m, 3)), jnp.float32)
        gamma = float(rng.uniform(0.01, 1.0))
        kw = dict(affinity_dtype=affinity_dtype, block_m=block_m)
        np.testing.assert_allclose(
            ops.nystrom_colsum(x, z, gamma, **kw),
            ref.nystrom_colsum_ref(x, z, gamma,
                                   affinity_dtype=affinity_dtype),
            rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(
            ops.nystrom_gram(x, z, gamma, u, wis, **kw),
            ref.nystrom_gram_ref(x, z, gamma, u, wis,
                                 affinity_dtype=affinity_dtype),
            rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(
            ops.nystrom_extension(x, z, gamma, u, proj, **kw),
            ref.nystrom_extension_ref(x, z, gamma, u, proj,
                                      affinity_dtype=affinity_dtype),
            rtol=5e-3, atol=5e-3)
