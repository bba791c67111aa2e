"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — essential because the dry-run
forces 512 placeholder host devices while tests/benches must see 1.

Every mesh is built with ``AxisType.Auto`` axes: the code under them
(GSPMD ``jit`` with sharding hints, ``shard_map`` bodies, k-means'
``onehot.T @ x`` on a row-sharded embedding) leaves the partitioning of
contractions to the compiler, which ``jax.make_mesh``'s default Explicit
axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e target: 16x16 (256 chips) per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 1) -> Mesh:
    """Small mesh over however many (possibly forced-host) devices exist."""
    if pod > 1:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def make_cohort_mesh(num_devices: int | None = None) -> Mesh:
    """1-D mesh for the sharded cohort-selection engine.

    The distributed Nyström path shards CLIENT ROWS over the single
    ``"clients"`` axis (the m-sized landmark problem is replicated), so
    by default the cohort mesh is flat over every visible device — on a
    TPU host that is all chips; under
    ``--xla_force_host_platform_device_count`` the forced host devices.
    ``num_devices`` takes the first that many devices instead (1 pins
    the engine to one chip on a multi-chip host).
    """
    devices = jax.devices()
    n = num_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"cohort mesh of {n} devices requested, "
                         f"{len(devices)} visible")
    return _auto_mesh((n,), ("clients",), devices=devices[:n])
