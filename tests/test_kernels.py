"""Per-kernel shape/dtype sweeps + assert_allclose vs the ref.py oracles
(interpret=True executes the kernel bodies in Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("n,m,d", [(16, 16, 4), (100, 70, 16), (129, 65, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_dist_sweep(n, m, d, dtype):
    x = jax.random.normal(KEY, (n, d), dtype)
    y = jax.random.normal(jax.random.fold_in(KEY, 1), (m, d), dtype)
    got = ops.pairwise_sq_dists(x, y, block_m=32, block_n=32)
    want = ref.pairwise_sq_dists_ref(x, y)
    atol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=1e-2)


@pytest.mark.parametrize("n", [31, 64, 130])
def test_rbf_affinity_sweep(n):
    x = jax.random.normal(KEY, (n, 8))
    got = ops.rbf_affinity(x, 0.7, block_m=32, block_n=32)
    want = ref.rbf_affinity_ref(x, 0.7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,m,d", [(16, 16, 4), (100, 33, 16), (129, 65, 8)])
def test_rbf_cross_affinity_sweep(n, m, d):
    """Rectangular Nyström cross-affinity block vs the jnp oracle."""
    x = jax.random.normal(KEY, (n, d))
    y = jax.random.normal(jax.random.fold_in(KEY, 1), (m, d))
    got = ops.rbf_cross_affinity(x, y, 0.4, block_m=32, block_n=32)
    want = ref.rbf_cross_affinity_ref(x, y, 0.4)
    assert got.shape == (n, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_rbf_cross_affinity_self_keeps_unit_diagonal():
    """Unlike the square affinity kernel, the cross block has no
    zero-diagonal convention: identical rows give affinity 1."""
    x = jax.random.normal(KEY, (40, 8))
    got = np.asarray(ops.rbf_cross_affinity(x, x, 0.7, block_m=32,
                                            block_n=32))
    np.testing.assert_allclose(np.diag(got), 1.0, atol=1e-5)


@pytest.mark.parametrize("S,H,K,dh", [(33, 4, 4, 16), (64, 8, 2, 32),
                                      (50, 4, 1, 16)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(S, H, K, dh, causal):
    q = jax.random.normal(KEY, (2, S, H, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, S, K, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, S, K, dh))
    got = ops.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(KEY, (1, 32, 2, 16), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 32, 2, 16), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 32, 2, 16), dtype)
    got = ops.flash_attention(q, k, v, block_q=16, block_k=16)
    want = ref.attention_ref(q, k, v)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-5 if dtype == jnp.float32 else 0.05)


def test_flash_attention_window():
    S = 48
    q = jax.random.normal(KEY, (1, S, 2, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, S, 2, 16))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, S, 2, 16))
    got = ops.flash_attention(q, k, v, causal=True, window=8,
                              block_q=16, block_k=16)
    want = ref.attention_ref(q, k, v, causal=True, window=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("Q,H,P,G,N", [(8, 2, 8, 1, 8), (16, 4, 8, 2, 12),
                                       (32, 8, 16, 1, 16)])
def test_ssd_chunk_sweep(Q, H, P, G, N):
    B, c = 2, 3
    xdt = jax.random.normal(KEY, (B, c, Q, H, P))
    cs = jnp.cumsum(-jnp.abs(jax.random.normal(
        jax.random.fold_in(KEY, 1), (B, c, Q, H))), axis=2)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 2), (B, c, Q, G, N))
    Cm = jax.random.normal(jax.random.fold_in(KEY, 3), (B, c, Q, G, N))
    y, st = ops.ssd_chunk(xdt, cs, Bm, Cm)
    y_r, st_r = ref.ssd_chunk_ref(xdt, cs, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_r), atol=1e-4)


def test_blocked_jnp_attention_matches_flash_kernel():
    """The model's jnp blocked path and the Pallas kernel are twins."""
    from repro.models.attention import blocked_attention
    S = 40
    q = jax.random.normal(KEY, (1, S, 4, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, S, 2, 16))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, S, 2, 16))
    a = blocked_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
    b = ops.flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_attention_ref_oracle_matches_attention_ref():
    """Regression (repro-lint pallas-ref-oracle): the flash kernel's
    same-named oracle exists in ref.py and equals the naive attention."""
    q = jax.random.normal(KEY, (1, 16, 4, 8))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 16, 2, 8))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 16, 2, 8))
    got = ref.flash_attention_ref(q, k, v, causal=True, window=8)
    want = ref.attention_ref(q, k, v, causal=True, window=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_interpret_mode_only_on_cpu(monkeypatch):
    """CPU interprets, TPU compiles, and any other backend is refused
    rather than silently interpreted."""
    from repro.kernels import ops as kops
    for backend, interpret in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert kops._interpret() is interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        kops._interpret()
