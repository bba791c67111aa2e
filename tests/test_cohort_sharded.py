"""Multi-device correctness of the sharded cohort engine.

Run single-device these tests exercise the shard_map path on a 1-way
mesh; the CI "sharded" job re-runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so every psum /
padding / replication path sees a real 8-way mesh.  The subprocess test
forces the 8-device regime even from a single-device parent.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cohort import (CohortConfig, CohortEngine,
                          nystrom_from_landmarks,
                          sharded_nystrom_from_landmarks,
                          uniform_landmarks)
from repro.core.kmeans import kmeans
from repro.launch.mesh import make_cohort_mesh

KEY = jax.random.PRNGKey(0)


def blobs(n=509, k=4, sep=8.0, d=8, seed=0):
    # deliberately not divisible by typical mesh sizes: exercises the
    # pad-and-mask path on every run
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * sep
    labels = rng.integers(0, k, n)
    x = (centers[labels] + rng.normal(size=(n, d))).astype(np.float32)
    return x, labels


def same_partition(a, b):
    return bool(np.all((a[:, None] == a[None, :])
                       == (b[:, None] == b[None, :])))


def test_ci_forced_device_count_wiring():
    """When the CI sharded job forces 8 host devices, jax must see them
    (catches the flag being set after jax initialization)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count=8" in flags:
        assert len(jax.devices()) == 8


def test_cohort_mesh_device_count_and_auto_axes():
    """``num_devices`` takes the first devices (1 pins one chip on a
    multi-chip host); asking for more than exist is refused; the axis
    is Auto so k-means' contraction over sharded rows partitions."""
    from jax.sharding import AxisType
    one = make_cohort_mesh(1)
    assert one.devices.size == 1 and one.devices[0] == jax.devices()[0]
    assert make_cohort_mesh().devices.size == len(jax.devices())
    assert one.axis_types == (AxisType.Auto,)
    with pytest.raises(ValueError, match="requested"):
        make_cohort_mesh(len(jax.devices()) + 1)


def test_sharded_allclose_to_single_device_nystrom():
    """Acceptance: identical landmarks + bandwidth -> the sharded path
    reproduces the single-device Nyström embedding to f32 reduction
    tolerance (spectrum) and the identical clustering."""
    x, _ = blobs()
    x = jnp.asarray(x)
    idx = uniform_landmarks(jax.random.PRNGKey(1), x, 64)
    y1, ev1, *_ = nystrom_from_landmarks(x, idx, 4, 0.05)
    y2, ev2, *_ = sharded_nystrom_from_landmarks(
        x, idx, 4, 0.05, make_cohort_mesh())
    np.testing.assert_allclose(np.asarray(ev1), np.asarray(ev2),
                               atol=1e-4)
    a1, _ = kmeans(jax.random.PRNGKey(2), y1, 4)
    a2, _ = kmeans(jax.random.PRNGKey(2), y2, 4)
    assert same_partition(np.asarray(a1), np.asarray(a2))


def test_engine_sharded_matches_nystrom_partition():
    """Same engine seed -> same content-derived keys -> same landmarks;
    the two methods must agree end-to-end (auto bandwidth included)."""
    x, labels = blobs()
    mk = lambda method: CohortEngine(
        CohortConfig(num_clusters=4, method=method, num_landmarks=64),
        seed=0)
    r1 = mk("nystrom").select(x)
    r2 = mk("sharded").select(x)
    assert r2.method == "sharded"
    assert same_partition(r1.assign, r2.assign)
    np.testing.assert_allclose(r1.evals, r2.evals, atol=5e-3)


def test_sharded_pallas_path_matches_jnp():
    """use_pallas must actually route through the kernels on the sharded
    path (regression: it used to be silently dropped) and agree with
    the jnp formula.  use_pallas now runs the streaming fused pipeline,
    whose tiled accumulation order differs from the materialized jnp
    composition — the partition and the leading (eigengap-informing)
    evals must still match tightly; the noise-dominated tail of the
    spectrum (near-null directions of W) gets a looser bound."""
    x, _ = blobs()
    k = 4
    mk = lambda pallas: CohortEngine(
        CohortConfig(num_clusters=k, method="sharded", num_landmarks=64,
                     use_pallas=pallas), seed=0)
    r_pal = mk(True).select(x)
    r_jnp = mk(False).select(x)
    assert same_partition(r_pal.assign, r_jnp.assign)
    # leading k evals (below the eigengap) are tightly pinned; from
    # index k upward the spectrum is the degenerate ~1 bulk, where the
    # near-null directions of W wander at the accumulation-order level
    np.testing.assert_allclose(r_pal.evals[:k], r_jnp.evals[:k], atol=1e-3)
    np.testing.assert_allclose(r_pal.evals, r_jnp.evals, atol=1e-2)


@pytest.mark.parametrize("affinity_dtype", ["f32", "bf16", "int8"])
def test_sharded_fused_quantized_matches_jnp_partition(affinity_dtype):
    """The streaming fused pipeline (use_pallas=True) at every tile
    precision must reproduce the jnp partition across the mesh — the
    per-shard fused accumulators compose with the two psums exactly
    like the materialized path (the last-step W⁻¹ᐟ² rotation is
    linear), including the padded-row masking on n=509."""
    x, labels = blobs()
    k = 4
    r_fused = CohortEngine(
        CohortConfig(num_clusters=k, method="sharded", num_landmarks=64,
                     use_pallas=True, affinity_dtype=affinity_dtype),
        seed=0).select(x)
    r_jnp = CohortEngine(
        CohortConfig(num_clusters=k, method="sharded", num_landmarks=64),
        seed=0).select(x)
    assert same_partition(r_fused.assign, r_jnp.assign)
    tol = 1e-3 if affinity_dtype == "f32" else 2e-2
    np.testing.assert_allclose(r_fused.evals[:k], r_jnp.evals[:k],
                               atol=tol)


def test_sharded_warm_start_equals_cold_start():
    """Warm-started sharded re-clustering after convergence must match a
    cold sharded solve on the same drifted embeddings."""
    x, _ = blobs()
    rng = np.random.default_rng(3)
    x2 = x + 0.01 * rng.normal(size=x.shape).astype(np.float32)
    cfg = lambda: CohortConfig(num_clusters=4, method="sharded",
                               num_landmarks=64, solver="subspace",
                               drift_threshold=0.1)
    warm_eng = CohortEngine(cfg(), seed=0)
    warm_eng.select(x)
    r_warm = warm_eng.select(x2)
    assert r_warm.source == "warm"
    r_cold = CohortEngine(cfg(), seed=0).select(x2)
    assert same_partition(r_warm.assign, r_cold.assign)
    np.testing.assert_allclose(r_warm.evals, r_cold.evals, atol=1e-2)


def test_cohort_server_dqn_policy_roundtrip_sharded():
    """DQN-policy serving through the sharded engine path: select ->
    observe_round -> drifted update, with consistent cohorts and
    advancing policy/engine stats (runs on the 8-way mesh in CI)."""
    from repro.launch.serve import CohortServer

    x, _ = blobs()
    n, d = x.shape
    srv = CohortServer(
        n, d, seed=0, policy="dqn",
        config=CohortConfig(num_clusters=4, method="sharded",
                            num_landmarks=64),
        dqn_overrides={"hidden": (32,), "eps_decay_steps": 10})
    srv.update_embeddings(np.arange(n), x)
    rng = np.random.default_rng(0)
    for r in range(3):
        ids, res = srv.select_cohort(16)
        assert res.method == "sharded"
        assert len(ids) == 16 and len(set(ids.tolist())) == 16
        srv.observe_round(0.5 + 0.1 * r)
        srv.update_embeddings(
            ids, srv.embeds[ids]
            + 0.01 * rng.normal(size=(16, d)).astype(np.float32))
    st = srv.stats()
    assert st["requests"] == 3 and st["rounds_observed"] == 3
    assert st["engine"]["solves"] == 3
    # drifted updates stay under the warm-start threshold
    assert st["engine"]["warm_starts"] >= 1
    assert st["policy"]["kind"] == "dqn"
    assert st["policy"]["train_calls"] == 3
    assert st["last_select"]["method"] == "sharded"


_SUBPROCESS_CHECK = """
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8, jax.devices()
from repro.cohort import nystrom_from_landmarks, \\
    sharded_nystrom_from_landmarks, uniform_landmarks
from repro.core.kmeans import kmeans
from repro.launch.mesh import make_cohort_mesh
rng = np.random.default_rng(0)
centers = rng.normal(size=(4, 8)) * 8
labels = rng.integers(0, 4, 509)
x = jnp.asarray((centers[labels]
                 + rng.normal(size=(509, 8))).astype(np.float32))
idx = uniform_landmarks(jax.random.PRNGKey(1), x, 64)
y1, ev1, *_ = nystrom_from_landmarks(x, idx, 4, 0.05)
y2, ev2, *_ = sharded_nystrom_from_landmarks(x, idx, 4, 0.05,
                                             make_cohort_mesh())
np.testing.assert_allclose(np.asarray(ev1), np.asarray(ev2), atol=1e-4)
a1, _ = kmeans(jax.random.PRNGKey(2), y1, 4)
a2, _ = kmeans(jax.random.PRNGKey(2), y2, 4)
a1, a2 = np.asarray(a1), np.asarray(a2)
assert np.all((a1[:, None] == a1[None, :]) == (a2[:, None] == a2[None, :]))
print("OK 8-device sharded == single-device")
"""


@pytest.mark.slow
def test_sharded_allclose_under_forced_8_host_devices():
    """Satellite: the 8-way mesh regime, regardless of parent devices.

    XLA flags must be set before jax initializes, so the check runs in a
    subprocess with the forced host-device count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_CHECK],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "OK 8-device sharded == single-device" in proc.stdout


@pytest.mark.slow
def test_cohort_engine_selects_100k_clients_sharded():
    """Acceptance: N = 100k cohort selection through the sharded engine
    (8-way host mesh in the CI sharded job)."""
    n, d, k = 100_000, 8, 8
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(k, d)).astype(np.float32) * 6
    labels = rng.integers(0, k, n)
    embeds = (centers[labels]
              + rng.normal(size=(n, d)).astype(np.float32))
    eng = CohortEngine(CohortConfig(num_clusters=k, method="sharded",
                                    num_landmarks=512), seed=0)
    res = eng.select(embeds)
    assert res.assign.shape == (n,)
    assert res.method == "sharded" and res.source == "cold"
    # every generator mode must land in its own non-trivial cluster
    assert len(np.unique(res.assign)) == k
    counts = np.bincount(res.assign, minlength=k)
    assert counts.min() > n // (4 * k)