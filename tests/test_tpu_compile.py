"""Ahead-of-time compiles of the cohort path's kernels for a TPU v5e.

Nothing runs: each test lowers a main-path kernel at a real population
(N = 10⁶ clients, d = 8) for a described ``v5e:2x2`` topology and lets
the TPU compiler accept or refuse it — VMEM overruns, misaligned blocks
and unpartitionable kernels surface here, where interpret mode on the
CPU cannot see them.  The topology is described inside a module fixture
(never at import), and every test skips when it cannot be described.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import affinity_pallas, nystrom_pallas, ops

N, D, K = 1_000_000, 8, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure: no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("affinity_dtype", ["f32", "int8"])
@pytest.mark.parametrize("m", [64, 2048])
@pytest.mark.parametrize("stage", ["colsum", "gram", "extension"])
def test_fused_nystrom_pass_compiles(one_chip, stage, m, affinity_dtype):
    s = functools.partial(_spec, one_chip)
    kw = dict(affinity_dtype=affinity_dtype, interpret=False)
    if stage == "colsum":
        fn = functools.partial(nystrom_pallas.nystrom_colsum_pallas, **kw)
        args = (s(N, D), s(m, D), s(), s(N))
    elif stage == "gram":
        fn = functools.partial(nystrom_pallas.nystrom_gram_pallas, **kw)
        args = (s(N, D), s(m, D), s(), s(m), s(m, m), s(N))
    else:
        fn = functools.partial(nystrom_pallas.nystrom_extension_pallas, **kw)
        args = (s(N, D), s(m, D), s(), s(m), s(m, K), s(N))
    _compile(fn, *args)


def test_panel_matmul_compiles_at_max_landmarks(one_chip):
    m = nystrom_pallas.MAX_LANDMARKS
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(nystrom_pallas.panel_matmul_pallas,
                           block_rows=2048, interpret=False)
    _compile(fn, s(m, m), s(m, 64))


def test_rbf_cross_affinity_compiles(one_chip):
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(affinity_pallas.rbf_cross_affinity_pallas,
                           interpret=False)
    _compile(fn, s(N, D), s(64, D), s())


@pytest.mark.parametrize("fused", [True, False])
def test_sharded_shard_body_compiles_on_2x2_mesh(topo, monkeypatch, fused):
    """The cohort engine's shard_map body over a 4-chip row-sharded mesh.

    The kernel wrappers pick interpret mode from the host's CPU backend,
    so the test steers them to compiled kernels, as on the chip."""
    from repro.cohort.sharded import _build_sharded_fn

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]), ("clients",),
                axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, P("clients", None))
    rep = NamedSharding(mesh, P())
    m = 64
    fn = _build_sharded_fn(mesh, K, "eigh", False, 30, 2048,
                           use_pallas=fused, fused=fused)
    with jax.default_matmul_precision("highest"):     # as the engine runs it
        compiled = fn.lower(
            _spec(rows, N, D), _spec(NamedSharding(mesh, P("clients")), N),
            _spec(rep, m, D), _spec(rep, m, m), _spec(rep), _spec(rep, m, K),
        ).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == fused
    assert "all-reduce" in text            # the two psums over row shards
