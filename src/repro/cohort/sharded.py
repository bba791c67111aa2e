"""Mesh-sharded distributed Nyström: the 10⁵–10⁸-client path.

The (N, m) cross-affinity is the only N-sized object in the landmark
pipeline, so it is the only thing worth distributing: client rows are
sharded over a 1-D device mesh (``launch.mesh.make_cohort_mesh``) with
``shard_map``, each device computing its own (N/D, m) panel of C and S
and its rows of the output embedding V.  The m-sized pieces — the
landmark block W, its inverse square root, and the normalized operator
M — are replicated: W is factored once on the host (dense eigh, or the
blocked subspace solver of ``cohort/eigensolver.py`` when m ≥ 10⁴), and
M is assembled from an all-reduced SᵀS (one ``psum``) so every device
solves the identical m×m eigenproblem.  Communication per round is
exactly one (m,) psum + one (m, m) psum — independent of N.

Row counts that don't divide the mesh are zero-padded and masked: padded
rows contribute nothing to the column sums or SᵀS and are sliced off the
output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.cohort.nystrom import (_nystrom_core, _nystrom_core_fused,
                                  landmark_block_isqrt)
from repro.core.spectral import cross_affinity

# jitted shard_map closures keyed on (mesh, k, mm_solver, warm, iters,
# block_rows, use_pallas, fused, affinity_dtype) — rebuilding the
# closure per call would retrace every round.
_SHARDED_FNS: dict = {}


def _build_sharded_fn(mesh, k: int, mm_solver: str, warm: bool,
                      iters: int, block_rows: int, use_pallas: bool,
                      fused: bool = False, affinity_dtype: str = "f32"):
    axis = mesh.axis_names[0]

    def body(x_s, mask_s, z, w_isqrt, gamma, mm_q0):
        if fused:
            # streaming pipeline: each shard's (N/D, m) C panel lives
            # only tile-by-tile in VMEM; the same two psums (col, SᵀS)
            # fire inside the fused core — the Gram pass's W⁻¹ᐟ²
            # rotation is linear, so per-shard rotated Grams sum
            # to the rotated global Gram.
            return _nystrom_core_fused(
                x_s, z, gamma, w_isqrt, k, mask=mask_s, axis_name=axis,
                affinity_dtype=affinity_dtype, mm_solver=mm_solver,
                mm_iters=iters, mm_q0=mm_q0 if warm else None,
                key=None, block_rows=block_rows)
        c = cross_affinity(x_s, z, gamma=gamma, use_pallas=use_pallas)
        c = c * mask_s[:, None]
        return _nystrom_core(
            c, w_isqrt, k, axis_name=axis, mm_solver=mm_solver,
            mm_iters=iters, mm_q0=mm_q0 if warm else None,
            key=None, block_rows=block_rows)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(), P(), P(), P()),
        out_specs=(P(axis, None), P(), P()),
        # pallas_call has no varying-manual-axes rule; the replicated
        # (P()) outputs are psum-derived either way, so the check adds
        # nothing on the kernel path
        check_vma=not (use_pallas or fused))
    return jax.jit(fn)


def sharded_nystrom_from_landmarks(x, idx, k: int, gamma, mesh, *,
                                   use_pallas: bool = False,
                                   fused: bool = False,
                                   affinity_dtype: str = "f32",
                                   w_solver: str = "eigh",
                                   w_rank: int | None = None,
                                   mm_solver: str = "eigh",
                                   iters: int = 30, w_q0=None, mm_q0=None,
                                   key=None, block_rows: int = 2048):
    """Distributed twin of ``nystrom.nystrom_from_landmarks``.

    Same signature plus ``mesh`` (a 1-D mesh whose single axis shards
    client rows); same ``(y, evals, mm_basis, w_basis)`` return contract,
    with ``y`` materialized as a global array sharded over the mesh.
    Numerically the two paths differ only by the float summation order
    of the two psums, so outputs agree to f32 reduction tolerance.
    ``fused=True`` swaps the shard body for the streaming Pallas core
    (``affinity_dtype`` tile precision; no per-shard (N/D, m) C panel in
    HBM) — same psum structure, so the mesh communication is unchanged.
    """
    n = x.shape[0]
    x = jnp.asarray(x, jnp.float32)
    z = x[idx]
    if key is not None:
        w_key, mm_key = jax.random.split(key)
    else:
        w_key = mm_key = None
    # W on the same backend as the sharded C panels (see nystrom.py on
    # backend consistency inside the degenerate leading eigenspace)
    if fused:
        from repro.kernels import ops as kernel_ops
        w = kernel_ops.quantized_cross_affinity(
            z, z, gamma, affinity_dtype=affinity_dtype)
    else:
        w = cross_affinity(z, z, gamma=gamma, use_pallas=use_pallas)
    w_isqrt, w_basis = landmark_block_isqrt(
        z, gamma, w=w,
        w_solver=w_solver, w_rank=w_rank, iters=iters,
        w_q0=w_q0, key=w_key, block_rows=block_rows,
        use_pallas=fused or use_pallas)

    num_shards = mesh.devices.size
    pad = (-n) % num_shards
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    mask = (jnp.arange(n + pad) < n).astype(jnp.float32)

    m = int(idx.shape[0])
    warm = mm_q0 is not None
    if warm:
        q0 = jnp.asarray(mm_q0, jnp.float32)
    elif mm_solver == "subspace":
        q0 = jax.random.normal(mm_key if mm_key is not None
                               else jax.random.PRNGKey(0), (m, k),
                               jnp.float32)
    else:
        q0 = jnp.zeros((m, k), jnp.float32)        # unused placeholder

    cache_key = (mesh, k, mm_solver, warm or mm_solver == "subspace",
                 iters, block_rows, use_pallas, fused, affinity_dtype)
    if cache_key not in _SHARDED_FNS:
        _SHARDED_FNS[cache_key] = _build_sharded_fn(
            mesh, k, mm_solver, warm or mm_solver == "subspace", iters,
            block_rows, use_pallas, fused, affinity_dtype)
    y, evals, basis = _SHARDED_FNS[cache_key](
        xp, mask, z, w_isqrt, jnp.asarray(gamma, jnp.float32), q0)
    return y[:n], evals, basis, w_basis
