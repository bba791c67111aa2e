"""Jitted public wrappers for the Pallas kernels.

Backend selection: on TPU the compiled kernels run natively; on CPU
``interpret=True`` executes the kernel bodies in Python for correctness
validation.  Any other backend is an error, never a silent interpreter.  ``set_use_pallas`` flips the model
substrate between the pure-jnp paths and the kernels globally; the
toggle is lock-guarded (serving threads flip it around probe solves) and
``use_pallas_scoped`` restores the previous value on exit.
"""

from __future__ import annotations

import contextlib
import threading

import jax

from repro.kernels.affinity_pallas import (pairwise_sq_dists_pallas,
                                           rbf_affinity_pallas,
                                           rbf_cross_affinity_pallas)
from repro.kernels.flash_attention_pallas import flash_attention_pallas
from repro.kernels.nystrom_pallas import (nystrom_colsum_pallas,
                                          nystrom_extension_pallas,
                                          nystrom_gram_pallas,
                                          panel_matmul_pallas,
                                          quantized_cross_affinity_pallas)
from repro.kernels.ssd_pallas import ssd_chunk_pallas


class _PallasToggle:
    """Process-wide substrate switch, safe under concurrent serving threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._flag = False  # guarded-by: _lock

    def get(self) -> bool:
        with self._lock:
            return self._flag

    def swap(self, flag: bool) -> bool:
        """Set the flag, returning the value it replaced (atomically)."""
        with self._lock:
            prev = self._flag
            self._flag = bool(flag)
        return prev


_TOGGLE = _PallasToggle()


def set_use_pallas(flag: bool) -> None:
    _TOGGLE.swap(flag)


def use_pallas() -> bool:
    return _TOGGLE.get()


@contextlib.contextmanager
def use_pallas_scoped(flag: bool = True):
    """Scoped substrate flip: restores the value observed at entry.

    The swap in/out is atomic, but two threads scoping different values
    over the same window still race on the shared flag — per-call
    ``use_pallas=`` arguments are the per-thread mechanism; this is for
    tests and single-threaded tools.
    """
    prev = _TOGGLE.swap(flag)
    try:
        yield
    finally:
        _TOGGLE.swap(prev)


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on TPU or interpreted on CPU; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


def pairwise_sq_dists(x, y, **kw):
    return pairwise_sq_dists_pallas(x, y, interpret=_interpret(), **kw)


def rbf_affinity(x, gamma, **kw):
    return rbf_affinity_pallas(x, gamma, interpret=_interpret(), **kw)


def rbf_cross_affinity(x, y, gamma, **kw):
    return rbf_cross_affinity_pallas(x, y, gamma, interpret=_interpret(),
                                     **kw)


def nystrom_colsum(x, z, gamma, mask=None, **kw):
    return nystrom_colsum_pallas(x, z, gamma, mask,
                                 interpret=_interpret(), **kw)


def nystrom_gram(x, z, gamma, u, w_isqrt, mask=None, **kw):
    return nystrom_gram_pallas(x, z, gamma, u, w_isqrt, mask,
                               interpret=_interpret(), **kw)


def nystrom_extension(x, z, gamma, u, proj, mask=None, **kw):
    return nystrom_extension_pallas(x, z, gamma, u, proj, mask,
                                    interpret=_interpret(), **kw)


def panel_matmul(w, q, **kw):
    return panel_matmul_pallas(w, q, interpret=_interpret(), **kw)


def quantized_cross_affinity(x, y, gamma, **kw):
    return quantized_cross_affinity_pallas(x, y, gamma,
                                           interpret=_interpret(), **kw)


def flash_attention(q, k, v, **kw):
    return flash_attention_pallas(q, k, v, interpret=_interpret(), **kw)


def ssd_chunk(xdt, cs, Bm, Cm, **kw):
    return ssd_chunk_pallas(xdt, cs, Bm, Cm, interpret=_interpret(), **kw)
