"""Streaming fused Nyström pipeline: C→S→SᵀS with no (N, m) in HBM.

``cohort/nystrom.py::_nystrom_core`` composes the landmark extension from
jnp ops around one Pallas affinity kernel, which materializes the (N, m)
cross-affinity C and re-reads it from HBM three more times (column sum,
degree scaling, SᵀS, extension).  At N = 10⁵–10⁸ the select is memory-
bound, so these kernels recompute the C tile from the (block_m, d) row
panel each pass instead of ever writing it out — three grid sweeps over
row panels, each tile living and dying in VMEM:

1. ``nystrom_colsum_pallas``   — affinity tile + column sum, accumulating
   ``col = Σᵢ C_ij`` into a single (1, m) output block.
2. ``nystrom_gram_pallas``     — recompute the tile, apply the
   ``rsqrt(d̂)`` degree scaling in-register (``d̂ = C·u`` folds the
   m-sized ``u = W⁻¹ᐟ²(W⁻¹ᐟ² col)`` the caller derives from pass 1),
   and accumulate the (m, m) ``SᵀS`` Gram across the grid, one (m, bn)
   column block per outer grid step; the wrapper then rotates by
   ``W⁻¹ᐟ²`` — rotation is linear, so the per-shard ``psum``
   composition of ``cohort/sharded.py`` is unchanged:
   ``psum(W⁻¹ᐟ² SᵀS_s W⁻¹ᐟ²) = W⁻¹ᐟ² (Σ_s SᵀS_s) W⁻¹ᐟ²``.
3. ``nystrom_extension_pallas`` — recompute the tile a third time and
   emit the row-normalized embedding ``V = S · proj`` directly, where
   ``proj = (W⁻¹ᐟ² U)·rsqrt(λ)`` is the precomputed (m, k) projector.

FLOPs triple on the affinity tile (recomputed 3×) but HBM traffic drops
from ~7 (N, m) transfers to the (N, d) input read per pass — the right
trade on every memory-bound backend.

Quantized affinity (the AQT idiom): ``affinity_dtype`` selects the tile
matmul precision — ``"f32"`` (exact), ``"bf16"`` (bf16 operands, f32 MXU
accumulation), or ``"int8"`` (per-ROW amax/127 scales so the quantization
grid is independent of the tile partition, int8×int8→int32 MXU dot,
rescale by ``s_x·s_zᵀ``).  Row norms are taken from the same (de)quantized
operands as the cross term so d² stays a true squared distance (≥ 0).

Every wrapper takes ``interpret=`` (CPU CI runs the kernels in interpret
mode) and has a matching ``*_ref`` oracle in ``kernels/ref.py``.

A zero/one row ``mask`` (n,) input covers both the wrapper's own row
padding and the global padding of the ``shard_map`` path: a masked row
contributes a zero row of C, hence nothing to ``col`` or ``SᵀS``, and a
zero (later sliced-off) row of V.

VMEM sizing: every (rows, m) tile is capped at ``_TILE_BYTES`` by
shrinking the row panel as m grows (``_row_block``), and the Gram's
(m, m) accumulator is split into (m, bn) column blocks of at most
``_GRAM_BLOCK_BYTES``, so the passes compile for a v5e chip at every
m up to ``MAX_LANDMARKS``; the engine refuses larger m before tracing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12      # degree / row-norm floor — matches cohort/nystrom.py
_QEPS = 1e-8      # int8 scale floor for all-zero rows

AFFINITY_DTYPES = ("f32", "bf16", "int8")

#: largest landmark count the fused passes and the panel matmul compile
#: for on a v5e chip (tests/test_tpu_compile.py holds them to it)
MAX_LANDMARKS = 4096
_TILE_BYTES = 2 << 20          # one f32 (rows, m) tile in VMEM
_GRAM_BLOCK_BYTES = 8 << 20    # one f32 (m, bn) Gram column block
# scoped VMEM for these kernels: a handful of tiles plus double-buffered
# blocks, well inside a v5e core's 128 MiB
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)


def _dot(a, b, dims):
    """f32 tile matmul at full f32 precision (a TPU's default f32 matmul
    is one bf16 pass), accumulated in f32."""
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _quantize_rows(a):
    """Per-row symmetric int8 quantization: (values, scales (rows, 1))."""
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0,
                        _QEPS)
    q = jnp.clip(jnp.round(a / scale), -127.0, 127.0)
    return q, scale


def _affinity_tile(x, z, gamma, affinity_dtype: str):
    """One (bm, bn) RBF cross-affinity tile at the requested precision.

    Same formula as ``affinity_pallas._cross_rbf_kernel``:
    exp(-γ·max(‖x‖² + ‖z‖² − 2·x·zᵀ, 0)), f32 output.  For quantized
    dtypes the norms are computed from the SAME rounded operands as the
    cross term, so d² is the exact squared distance of the quantized
    points (never negative by construction).
    """
    x = x.astype(jnp.float32)
    z = z.astype(jnp.float32)
    if affinity_dtype == "f32":
        xc, zc = x, z
        xy = _dot(x, z, (((1,), (1,)), ((), ())))
    elif affinity_dtype == "bf16":
        xb = x.astype(jnp.bfloat16)
        zb = z.astype(jnp.bfloat16)
        xc = xb.astype(jnp.float32)
        zc = zb.astype(jnp.float32)
        xy = jax.lax.dot_general(xb, zb, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.DEFAULT,
                                 preferred_element_type=jnp.float32)
    elif affinity_dtype == "int8":
        qx, sx = _quantize_rows(x)                 # (bm, d), (bm, 1)
        qz, sz = _quantize_rows(z)                 # (bn, d), (bn, 1)
        acc = jax.lax.dot_general(qx.astype(jnp.int8), qz.astype(jnp.int8),
                                  (((1,), (1,)), ((), ())),
                                  precision=jax.lax.Precision.DEFAULT,
                                  preferred_element_type=jnp.int32)
        xy = acc.astype(jnp.float32) * (sx * sz.T)
        xc = qx * sx
        zc = qz * sz
    else:
        raise ValueError(f"unknown affinity_dtype {affinity_dtype!r}; "
                         f"expected one of {AFFINITY_DTYPES}")
    xx = jnp.sum(xc * xc, axis=-1)[:, None]
    zz = jnp.sum(zc * zc, axis=-1)[None, :]
    d2 = jnp.maximum(xx + zz - 2.0 * xy, 0.0)
    return jnp.exp(-gamma * d2)


def _s_tile(c, u):
    """Degree-normalized tile S = C·rsqrt(max(C·u, eps)) in-register."""
    d_hat = _dot(c, u, (((1,), (0,)), ((), ())))                 # (bm, 1)
    return c * jax.lax.rsqrt(jnp.maximum(d_hat, _EPS))


# --------------------------------------------------------------------------
# pass 1: fused affinity + column sum
# --------------------------------------------------------------------------

def _colsum_kernel(x_ref, z_ref, g_ref, mask_ref, o_ref, *, affinity_dtype):
    i = pl.program_id(0)
    c = _affinity_tile(x_ref[...], z_ref[...], g_ref[0, 0], affinity_dtype)
    c = c * mask_ref[...]                                  # (bm, 1) bcast
    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    o_ref[...] += jnp.sum(c, axis=0, keepdims=True)        # (1, m)


# --------------------------------------------------------------------------
# pass 2: fused affinity + degree scaling + SᵀS Gram, in column blocks
# --------------------------------------------------------------------------

def _gram_kernel(x_ref, z_ref, zj_ref, g_ref, u_ref, mask_ref, o_ref, *,
                 affinity_dtype):
    # grid (column block j, row panel i): the full-width tile gives the
    # degree scaling and the Gram's rows, the (bm, bn) tile of landmark
    # block j its columns
    i = pl.program_id(1)
    x, gamma, mask = x_ref[...], g_ref[0, 0], mask_ref[...]
    c = _affinity_tile(x, z_ref[...], gamma, affinity_dtype) * mask
    d_hat = _dot(c, u_ref[...], (((1,), (0,)), ((), ())))
    scale = jax.lax.rsqrt(jnp.maximum(d_hat, _EPS))             # (bm, 1)
    s_j = _affinity_tile(x, zj_ref[...], gamma, affinity_dtype) * mask
    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    o_ref[...] += _dot(c * scale, s_j * scale, (((0,), (0,)), ((), ())))


# --------------------------------------------------------------------------
# pass 3: fused affinity + degree scaling + projection + row normalization
# --------------------------------------------------------------------------

def _extension_kernel(x_ref, z_ref, g_ref, u_ref, proj_ref, mask_ref, o_ref,
                      *, affinity_dtype):
    c = _affinity_tile(x_ref[...], z_ref[...], g_ref[0, 0], affinity_dtype)
    c = c * mask_ref[...]
    s = _s_tile(c, u_ref[...])
    v = _dot(s, proj_ref[...], (((1,), (0,)), ((), ())))         # (bm, k)
    norm = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
    o_ref[...] = v / jnp.maximum(norm, _EPS)


# --------------------------------------------------------------------------
# eigensolver row-panel matmul (subspace sweeps)
# --------------------------------------------------------------------------

def _panel_matmul_kernel(w_ref, q_ref, o_ref):
    o_ref[...] = _dot(w_ref[...], q_ref[...], (((1,), (0,)), ((), ())))


def _quant_cross_kernel(x_ref, y_ref, g_ref, o_ref, *, affinity_dtype):
    o_ref[...] = _affinity_tile(x_ref[...], y_ref[...], g_ref[0, 0],
                                affinity_dtype)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _row_block(n: int, block_m: int, width: int = 0) -> int:
    """Effective row-panel height for a (rows, width) f32 tile.

    Never pads small n up to a huge panel, and shrinks the panel as the
    tile widens so it stays within ``_TILE_BYTES`` of VMEM.
    """
    if width:
        block_m = min(block_m, max(8, _TILE_BYTES // (4 * width) // 8 * 8))
    return min(block_m, _round_up(max(n, 1), 8))


def _gram_col_block(m: int) -> int:
    """Column-block width of the (m, m) Gram accumulator."""
    if 4 * m * m <= _GRAM_BLOCK_BYTES:
        return m
    return max(128, _GRAM_BLOCK_BYTES // (4 * m) // 128 * 128)


def _pad_rows_mask(x, mask, bm):
    """Pad rows to a ``bm`` multiple; padded mask entries are zero."""
    n = x.shape[0]
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    mask = jnp.asarray(mask, jnp.float32).reshape(n, 1)
    pad = (-n) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    return x, mask


@functools.partial(jax.jit, static_argnames=("affinity_dtype", "block_m",
                                             "interpret"))
def nystrom_colsum_pallas(x, z, gamma, mask=None, *,
                          affinity_dtype: str = "f32", block_m: int = 1024,
                          interpret: bool = False):
    """Fused ``col = Σᵢ exp(-γ d²(xᵢ, z))·maskᵢ`` without materializing C.

    x: (n, d) rows, z: (m, d) landmarks, mask: optional (n,) zero/one
    rows.  Returns (m,) f32.  The (block_m, m) affinity tile exists only
    in VMEM.
    """
    n = x.shape[0]
    m = z.shape[0]
    bm = _row_block(n, block_m, m)
    xp, maskp = _pad_rows_mask(x, mask, bm)
    gamma_arr = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    kern = functools.partial(_colsum_kernel, affinity_dtype=affinity_dtype)
    d = x.shape[1]
    out = pl.pallas_call(
        kern,
        grid=(xp.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                  pl.BlockSpec((m, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(xp, z, gamma_arr, maskp)
    return out[0]


@functools.partial(jax.jit, static_argnames=("affinity_dtype", "block_m",
                                             "interpret"))
def nystrom_gram_pallas(x, z, gamma, u, w_isqrt, mask=None, *,
                        affinity_dtype: str = "f32", block_m: int = 1024,
                        interpret: bool = False):
    """Fused ``W⁻¹ᐟ² (SᵀS) W⁻¹ᐟ²`` where S is the degree-normalized C.

    ``u`` (m,) is ``W⁻¹ᐟ²(W⁻¹ᐟ² col)`` from pass 1 (globally psummed on
    the sharded path); ``w_isqrt`` (m, m).  Returns the rotated (m, m)
    Gram — symmetrize and eigensolve on the host side.  The kernel
    accumulates the unrotated SᵀS in (m, bn) column blocks; landmarks
    padded up to a block multiple get ``u = 0`` and are sliced off.
    """
    n = x.shape[0]
    m = z.shape[0]
    bm = _row_block(n, block_m, m)
    bn = _gram_col_block(m)
    m_pad = _round_up(m, bn)
    xp, maskp = _pad_rows_mask(x, mask, bm)
    gamma_arr = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    zp = jnp.pad(z, ((0, m_pad - m), (0, 0)))
    u2 = jnp.pad(jnp.asarray(u, jnp.float32), (0, m_pad - m)).reshape(
        m_pad, 1)
    kern = functools.partial(_gram_kernel, affinity_dtype=affinity_dtype)
    d = x.shape[1]
    sts = pl.pallas_call(
        kern,
        grid=(m_pad // bn, xp.shape[0] // bm),
        in_specs=[pl.BlockSpec((bm, d), lambda j, i: (i, 0)),
                  pl.BlockSpec((m_pad, d), lambda j, i: (0, 0)),
                  pl.BlockSpec((bn, d), lambda j, i: (j, 0)),
                  pl.BlockSpec((1, 1), lambda j, i: (0, 0)),
                  pl.BlockSpec((m_pad, 1), lambda j, i: (0, 0)),
                  pl.BlockSpec((bm, 1), lambda j, i: (i, 0))],
        out_specs=pl.BlockSpec((m_pad, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, m_pad), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(xp, zp, zp, gamma_arr, u2, maskp)[:m, :m]
    # W⁻¹ᐟ² rotation on the accumulated Gram — linear, so the sharded
    # psum over per-shard outputs still composes (see module doc)
    w_isqrt = jnp.asarray(w_isqrt, jnp.float32)
    return w_isqrt @ sts @ w_isqrt


@functools.partial(jax.jit, static_argnames=("affinity_dtype", "block_m",
                                             "interpret"))
def nystrom_extension_pallas(x, z, gamma, u, proj, mask=None, *,
                             affinity_dtype: str = "f32",
                             block_m: int = 1024, interpret: bool = False):
    """Fused row-normalized extension ``row_normalize(S · proj)``.

    ``proj`` (m, k) is ``(W⁻¹ᐟ² U_k)·rsqrt(λ_k)`` — the whole right-hand
    side of the Nyström extension collapsed to one matmul.  Returns
    (n, k) f32 with unit rows (masked/zero rows stay zero).
    """
    n = x.shape[0]
    m = z.shape[0]
    k = proj.shape[1]
    bm = _row_block(n, block_m, m)
    xp, maskp = _pad_rows_mask(x, mask, bm)
    gamma_arr = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    u2 = jnp.asarray(u, jnp.float32).reshape(m, 1)
    kern = functools.partial(_extension_kernel,
                             affinity_dtype=affinity_dtype)
    d = x.shape[1]
    out = pl.pallas_call(
        kern,
        grid=(xp.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                  pl.BlockSpec((m, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((m, 1), lambda i: (0, 0)),
                  pl.BlockSpec((m, k), lambda i: (0, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], k), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(xp, z, gamma_arr, u2, jnp.asarray(proj, jnp.float32), maskp)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def panel_matmul_pallas(w, q, *, block_rows: int = 2048,
                        interpret: bool = False):
    """Row-panel (m, p) @ (p, r) with the panel loop inside one kernel.

    The Pallas twin of ``cohort/eigensolver.py::_blocked_matmul``: the
    subspace sweep's W·Q product evaluated one (block_rows, p) panel at a
    time so peak residency stays O(block_rows·p), without round-tripping
    each panel through a separate XLA dispatch.  Panels of wide ``w``
    are shortened to fit VMEM (``_row_block``).
    """
    m, p = w.shape
    r = q.shape[1]
    bl = _row_block(m, block_rows, p)
    pad = (-m) % bl
    wp = jnp.pad(w.astype(jnp.float32), ((0, pad), (0, 0))) if pad \
        else w.astype(jnp.float32)
    out = pl.pallas_call(
        _panel_matmul_kernel,
        grid=(wp.shape[0] // bl,),
        in_specs=[pl.BlockSpec((bl, p), lambda i: (i, 0)),
                  pl.BlockSpec((p, r), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bl, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp.shape[0], r), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(wp, q.astype(jnp.float32))
    return out[:m]


@functools.partial(jax.jit, static_argnames=("affinity_dtype", "block_m",
                                             "interpret"))
def quantized_cross_affinity_pallas(x, y, gamma, *,
                                    affinity_dtype: str = "f32",
                                    block_m: int = 128,
                                    interpret: bool = False):
    """Materialized cross-affinity at a chosen tile precision.

    The m-sized companion of the streaming passes: the fused path builds
    its landmark block W = A(z, z) through the SAME quantized tile math
    (per-row scales make the result partition-independent), keeping W
    bit-consistent with the streamed C tiles.  ``"f32"`` reproduces
    ``rbf_cross_affinity_pallas`` exactly.
    """
    n = x.shape[0]
    m = y.shape[0]
    bm = _row_block(n, block_m, m)
    xp, _ = _pad_rows_mask(x, None, bm)
    gamma_arr = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    kern = functools.partial(_quant_cross_kernel,
                             affinity_dtype=affinity_dtype)
    d = x.shape[1]
    out = pl.pallas_call(
        kern,
        grid=(xp.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                  pl.BlockSpec((m, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], m), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(xp, y, gamma_arr)
    return out[:n]
