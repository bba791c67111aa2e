"""Plain Nyström spectral clustering, the yardstick for ``correct``.

It imports nothing of the program.  Given a table, a landmark set and a
bandwidth γ it computes the normalized-Laplacian Nyström embedding of
Fowlkes et al. (2004) the straightforward way:

    C   = exp(-γ·d²(x, z))                 (N, m), float64 on the host
    W   = C[landmarks]                     (m, m) landmark block
    W⁻¹ᐟ² from eigh(W), eigenvalues under max(1e-6, m·ε)·λ_max dropped
    col = Σᵢ Cᵢ,   u = W⁻¹ᐟ²(W⁻¹ᐟ² col),   d̂ = C·u
    S   = C / √d̂,  M = W⁻¹ᐟ² (SᵀS) W⁻¹ᐟ²,  λ = eigh(M), descending
    spectrum of the normalized Laplacian L = 1 − λ

The affinity is taken in float64 on the host and rounded to float32:
W⁻¹ᐟ² keeps eigenvalues down to ~10⁻⁵ of the largest, so the relative
error of an f32 ``exp`` on the chip (~5·10⁻⁶) would move the spectrum
by up to ~10⁻³.  The rest runs on the device in float32, row block by
row block, every product through ``dot``: ``highest_dot`` (full float32)
for the reference; ``high_dot`` (three bfloat16 passes, the precision one
step below) and ``default_dot`` (one pass) for a solve that drops the
precision the engine asks for.
"""

from __future__ import annotations

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12
_BLOCK_ELEMS = 1 << 24        # f32 entries of one (rows, m) block of C
_HOST_THREADS = 8
# rows per partial SᵀS: one TPU matmul contracting 10⁶ rows errs by
# ~3·10⁻⁵ even at HIGHEST, summed over 2,048-row slices by ~10⁻⁶
_GRAM_ROWS = 2048


def highest_dot(a, b):
    """a @ b in full float32."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def high_dot(a, b):
    """a @ b in three bfloat16 passes (hi·hi + hi·lo + lo·hi).

    A TPU has the precision itself (``Precision.HIGH``); elsewhere the
    passes are spelled out, since other backends compute f32 dots in
    full whatever precision is asked for.
    """
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return highest_dot(ah, bh) + (highest_dot(ah, bl) + highest_dot(al, bh))


def default_dot(a, b):
    """a @ b in one bfloat16 pass, a TPU's ``Precision.DEFAULT`` for f32."""
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.DEFAULT)
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


DOTS = {"highest": highest_dot, "high": high_dot, "default": default_dot}


def block_rows(m: int) -> int:
    """Rows per block of C: a multiple of the SᵀS slice."""
    return max(_GRAM_ROWS, min(65536, _BLOCK_ELEMS // m)
               // _GRAM_ROWS * _GRAM_ROWS)


def affinity(table: np.ndarray, z: np.ndarray, gamma: float,
             rows: int, dtype=np.float32) -> np.ndarray:
    """(N, m) RBF affinity, each entry computed in float64 and stored as
    ``dtype``."""
    z64 = np.asarray(z, np.float64)
    zz = np.sum(z64 * z64, axis=1)
    out = np.empty((len(table), len(z64)), dtype)

    def block(i):
        xb = np.asarray(table[i:i + rows], np.float64)
        d2 = np.sum(xb * xb, axis=1)[:, None] + zz[None, :] - 2.0 * (
            xb @ z64.T)
        np.maximum(d2, 0.0, out=d2)
        out[i:i + rows] = np.exp(-gamma * d2)

    with concurrent.futures.ThreadPoolExecutor(_HOST_THREADS) as pool:
        list(pool.map(block, range(0, len(table), rows)))
    return out


def _nblocks(cp, block):
    return cp.shape[0] // block


def _s_block(cb, u, dot):
    d_hat = dot(cb, u[:, None])
    return cb * jax.lax.rsqrt(jnp.maximum(d_hat, _EPS))


@functools.partial(jax.jit, static_argnames=("precision",))
def _gram(cp, u, *, precision):
    dot = DOTS[precision]

    def body(i, acc):
        s = _s_block(jax.lax.dynamic_slice_in_dim(cp, i * _GRAM_ROWS,
                                                  _GRAM_ROWS), u, dot)
        return acc + dot(s.T, s)

    m = cp.shape[1]
    return jax.lax.fori_loop(0, _nblocks(cp, _GRAM_ROWS), body,
                             jnp.zeros((m, m), jnp.float32))


@functools.partial(jax.jit, static_argnames=("precision",))
def _landmark_isqrt_u(w, col, *, precision):
    dot = DOTS[precision]
    w = 0.5 * (w + w.T)
    ew, uw = jnp.linalg.eigh(w)
    m = w.shape[0]
    floor = max(1e-6, m * float(jnp.finfo(jnp.float32).eps))
    inv = jnp.where(ew > floor * jnp.max(ew), 1.0 / jnp.maximum(ew, _EPS),
                    0.0)
    w_is = dot(uw * jnp.sqrt(inv)[None, :], uw.T)
    return w_is, dot(w_is, dot(w_is, col[:, None]))[:, 0]


@functools.partial(jax.jit, static_argnames=("precision",))
def _spectrum(w_is, sts, *, precision):
    dot = DOTS[precision]
    mm = dot(dot(w_is, sts), w_is)
    lam = jnp.linalg.eigvalsh(0.5 * (mm + mm.T))
    return 1.0 - lam[::-1]


def spectrum(table: np.ndarray, landmark_idx, gamma: float, *,
             precision: str = "highest", c: np.ndarray = None) -> np.ndarray:
    """Spectrum of L, ascending, for ``table``, the landmarks and γ.

    ``c`` passes an affinity already computed by :func:`affinity` for the
    same table, landmarks and γ.
    """
    n = len(table)
    idx = np.asarray(landmark_idx)
    m = len(idx)
    block = min(block_rows(m), -(-n // _GRAM_ROWS) * _GRAM_ROWS)
    if c is None:
        c = affinity(table, np.asarray(table)[idx], gamma, block)
    with jax.default_matmul_precision("highest"):
        cd = jnp.asarray(c, jnp.float32)
        col = jnp.sum(cd, axis=0)
        w_is, u = _landmark_isqrt_u(cd[jnp.asarray(idx)], col,
                                    precision=precision)
        cp = jnp.pad(cd, ((0, (-n) % block), (0, 0)))
        del cd
        sts = _gram(cp, u, precision=precision)
        return np.asarray(_spectrum(w_is, sts, precision=precision))
