"""Landmark-explicit Nyström embedding — the math core of the engine.

``nystrom_from_landmarks`` is the one-shot Nyström extension (Fowlkes et
al., 2004) factored so the LANDMARK SET IS AN INPUT, not sampled inside:
the engine owns landmark selection (uniform / leverage / k-means++, see
``cohort/landmarks.py``) and warm-start state, and both the single-device
path here and the mesh-sharded path (``cohort/sharded.py``) consume the
same ``_nystrom_core`` body.  The core is written against an optional
``axis_name`` so the only difference between the two paths is a pair of
``lax.psum`` reductions over the client-row shards:

    col  = Σ_i C_ij            (m,)   — psum over row shards
    SᵀS  = Σ_shards S_sᵀ S_s   (m, m) — psum over row shards

Everything m-sized (the landmark block W, its inverse square root, the
normalized operator M and its eigenbasis) is replicated; everything
N-sized (C, S, the output embedding V) stays sharded.

``repro.core.spectral.nystrom_spectral_embedding`` delegates here, so
there is exactly one implementation of the extension in the tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.cohort.eigensolver import isqrt_from_eigs, topk_eigh
from repro.core.kmeans import pairwise_sq_dists
from repro.core.spectral import cross_affinity, row_normalize

_EPS = 1e-12
_GRAM_ROWS = 2048      # rows per partial SᵀS product (see _gram)


def _gram(s, block: int = _GRAM_ROWS):
    """SᵀS accumulated over ``block``-row slices of ``s``.

    One matmul contracting all n rows loses accuracy on a TPU: at
    n = 10⁶ its relative error is 2.8e-5 even at HIGHEST precision,
    against 8e-7 summed over 2,000-row slices (v5e), and the difference
    moves the Nyström spectrum by up to 6e-3.  Summing per slice is what
    the fused Gram kernel does over its grid.
    """
    full = s.shape[0] // block
    # start from the ragged tail: its product carries the row shard's
    # varying-axis type into the loop (a zeros carry would not)
    tail = s[full * block:]
    acc = tail.T @ tail
    if full == 0:
        return acc

    def add(i, acc):
        blk = jax.lax.dynamic_slice_in_dim(s, i * block, block)
        return acc + blk.T @ blk

    return jax.lax.fori_loop(0, full, add, acc)


def _nystrom_core(c, w_isqrt, k: int, *, axis_name=None,
                  mm_solver: str = "eigh", mm_iters: int = 30,
                  mm_q0=None, key=None, block_rows: int = 2048):
    """Degree-normalize C, solve the m×m operator, extend to all rows.

    ``c`` is the (n_local, m) cross-affinity (the full (N, m) block on
    the single-device path, one row shard under ``shard_map``).  With
    ``axis_name`` set, the two cross-shard sums are ``psum``ed so every
    device sees the same m×m operator while its rows of S / V stay local.

    Returns ``(y_rownormed, evals_of_L_norm_ascending, mm_basis)`` where
    ``mm_basis`` is the top-k eigenbasis of M — the warm-start payload.
    """
    col = jnp.sum(c, axis=0)                                   # (m,)
    if axis_name is not None:
        col = jax.lax.psum(col, axis_name)
    # approximate degrees d̂ = C W⁺ (Cᵀ 1); W⁺ = W^{-1/2} W^{-1/2}
    d_hat = c @ (w_isqrt @ (w_isqrt @ col))
    s = c * jax.lax.rsqrt(jnp.maximum(d_hat, _EPS))[:, None]   # (n_l, m)
    sts = _gram(s)
    if axis_name is not None:
        sts = jax.lax.psum(sts, axis_name)
    mm = w_isqrt @ sts @ w_isqrt
    mm = 0.5 * (mm + mm.T)
    r = mm.shape[0] if mm_solver == "eigh" else k
    lam, top = topk_eigh(mm, r, solver=mm_solver, iters=mm_iters,
                         q0=mm_q0, key=key, block_rows=block_rows)
    basis = top[:, :k]
    v = (s @ (w_isqrt @ basis)) * jax.lax.rsqrt(
        jnp.maximum(lam[:k], _EPS))[None, :]                   # (n_l, k)
    evals = 1.0 - lam                                          # asc. L_norm
    return row_normalize(v), evals, basis


def _nystrom_core_fused(x, z, gamma, w_isqrt, k: int, *, mask=None,
                        axis_name=None, affinity_dtype: str = "f32",
                        mm_solver: str = "eigh", mm_iters: int = 30,
                        mm_q0=None, key=None, block_rows: int = 2048):
    """Streaming twin of ``_nystrom_core``: C never hits HBM.

    Same math, but the (n_local, m) cross-affinity is recomputed tile-by-
    tile inside three fused Pallas passes (``kernels/nystrom_pallas.py``)
    instead of being materialized and re-read: colsum → rotated SᵀS Gram
    → row-normalized extension.  ``x`` is the raw (n_local, d) rows (the
    affinity is fused in), ``mask`` zeroes padded rows, and the two
    ``psum`` points are identical to the unfused core — the Gram pass's
    ``W⁻¹ᐟ²·put·W⁻¹ᐟ²`` rotation is linear, so psum-of-rotated
    equals rotated-psum.  ``affinity_dtype`` picks the tile precision
    (f32 / bf16 / int8 — see the kernel module).
    """
    from repro.kernels import ops as kernel_ops
    col = kernel_ops.nystrom_colsum(x, z, gamma, mask,
                                    affinity_dtype=affinity_dtype)
    if axis_name is not None:
        col = jax.lax.psum(col, axis_name)
    u = w_isqrt @ (w_isqrt @ col)                              # (m,)
    mm = kernel_ops.nystrom_gram(x, z, gamma, u, w_isqrt, mask,
                                 affinity_dtype=affinity_dtype)
    if axis_name is not None:
        mm = jax.lax.psum(mm, axis_name)
    mm = 0.5 * (mm + mm.T)
    r = mm.shape[0] if mm_solver == "eigh" else k
    lam, top = topk_eigh(mm, r, solver=mm_solver, iters=mm_iters,
                         q0=mm_q0, key=key, block_rows=block_rows,
                         use_pallas=True)
    basis = top[:, :k]
    proj = (w_isqrt @ basis) * jax.lax.rsqrt(
        jnp.maximum(lam[:k], _EPS))[None, :]                   # (m, k)
    v = kernel_ops.nystrom_extension(x, z, gamma, u, proj, mask,
                                     affinity_dtype=affinity_dtype)
    evals = 1.0 - lam                                          # asc. L_norm
    return v, evals, basis


def landmark_block_isqrt(z, gamma, *, w=None, w_solver: str = "eigh",
                         w_rank: int | None = None, iters: int = 30,
                         w_q0=None, key=None, block_rows: int = 2048,
                         use_pallas: bool = False):
    """W^{-1/2} of the landmark affinity block, plus its eigenbasis.

    ``w`` overrides the affinity block (callers that already hold the
    landmark rows of C pass them to stay backend-consistent with C).
    ``w_solver="subspace"`` with ``w_rank`` r < m builds the rank-r
    pseudo-inverse square root from the blocked solver — the m ≥ 10⁴
    regime where dense eigh is not an option.  Returns
    ``(w_isqrt (m, m), w_basis (m, r))``.
    """
    m = z.shape[0]
    if w is None:
        w = jnp.exp(-gamma * pairwise_sq_dists(z, z))
    w = 0.5 * (w + w.T)
    r = m if w_solver == "eigh" else min(m, w_rank or m)
    ew, uw = topk_eigh(w, r, solver=w_solver, iters=iters, q0=w_q0,
                       key=key, block_rows=block_rows,
                       use_pallas=use_pallas)
    return isqrt_from_eigs(ew, uw), uw


# NOT jitted at this level: under jit XLA re-fuses the jnp cross-affinity
# while the Pallas call stays opaque, and the ~1e-7 accumulation
# differences rotate the (degenerate) leading eigenspace arbitrarily.
# Eager, interpret-mode Pallas is bit-identical to the jnp formula, and
# callers inside jit contexts (spectral_cluster) trace this anyway.
# The eager dispatch costs ~1.8x wall-clock at N=100k — at that scale
# use the sharded path (fully jitted; a 1-way mesh on one device),
# which the engine's "auto" method resolution does by default.
def nystrom_from_landmarks(x, idx, k: int, gamma, *,
                           use_pallas: bool = False,
                           fused: bool = False,
                           affinity_dtype: str = "f32",
                           w_solver: str = "eigh",
                           w_rank: int | None = None,
                           mm_solver: str = "eigh", iters: int = 30,
                           w_q0=None, mm_q0=None, key=None,
                           block_rows: int = 2048):
    """Nyström normalized-Laplacian embedding from an explicit landmark set.

    x: (n, d) points; idx: (m,) landmark indices into x; gamma: RBF
    bandwidth (explicit — the engine owns the heuristic so warm starts
    can pin it).  Returns ``(y, evals, mm_basis, w_basis)``:

    * ``y`` — (n, k) row-normalized embedding (rows of V);
    * ``evals`` — ascending spectrum of the approximate L_norm (length m
      for ``mm_solver="eigh"``, k for ``"subspace"``);
    * ``mm_basis`` / ``w_basis`` — the two eigenbases a later call can
      warm-start from (``mm_q0`` / ``w_q0``).

    ``fused=True`` runs the streaming Pallas pipeline instead — the
    (n, m) C block is never materialized and ``affinity_dtype`` selects
    the tile precision.  Numerically this is the same operator up to
    the tiled f32 summation order, which rotates the (degenerate)
    leading eigenspace: compare rotation-invariant quantities (``evals``,
    the ``y·yᵀ`` projector, cluster partitions), not raw embeddings.
    ``fused=False`` (the default) is the jnp-composed reference the
    tests pin the fused path against.
    """
    x = x.astype(jnp.float32)
    z = x[idx]
    if key is not None:
        w_key, mm_key = jax.random.split(key)
    else:
        w_key = mm_key = None
    if fused:
        from repro.kernels import ops as kernel_ops
        # W through the same quantized tile math as the streamed C
        # panels (per-row scales make it partition-independent), for the
        # same backend-consistency reason as the unfused ``c[idx]``.
        w = kernel_ops.quantized_cross_affinity(
            z, z, gamma, affinity_dtype=affinity_dtype)
        w_isqrt, w_basis = landmark_block_isqrt(
            z, gamma, w=w, w_solver=w_solver, w_rank=w_rank,
            iters=iters, w_q0=w_q0, key=w_key, block_rows=block_rows,
            use_pallas=True)
        y, evals, basis = _nystrom_core_fused(
            x, z, gamma, w_isqrt, k, affinity_dtype=affinity_dtype,
            mm_solver=mm_solver, mm_iters=iters, mm_q0=mm_q0,
            key=mm_key, block_rows=block_rows)
        return y, evals, basis, w_basis
    c = cross_affinity(x, z, gamma=gamma, use_pallas=use_pallas)  # (n, m)
    # W = the landmark rows of C (not recomputed from z): keeping W on
    # the same backend/accumulation as C keeps the two consistent inside
    # the degenerate leading eigenspace a well-separated clustering has.
    w_isqrt, w_basis = landmark_block_isqrt(
        z, gamma, w=c[idx], w_solver=w_solver, w_rank=w_rank,
        iters=iters, w_q0=w_q0, key=w_key, block_rows=block_rows)
    y, evals, basis = _nystrom_core(
        c, w_isqrt, k, mm_solver=mm_solver, mm_iters=iters, mm_q0=mm_q0,
        key=mm_key, block_rows=block_rows)
    return y, evals, basis, w_basis
