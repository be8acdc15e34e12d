"""Compile the main path's kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached. These tests refuse what interpret mode cannot
see — a kernel Mosaic does not lower, a block shape off the (8, 128)
tiling, a program that does not fit the chip's memory. They compile
only; nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9  # one v5e chip
NB, N = 256, 4096  # kernel compile size (n_b, n)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _shape(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> bool:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_device_kind_has_peaks(topo):
    from repro.spgemm.cost_model import DEVICE_PEAKS

    assert topo.devices[0].device_kind in DEVICE_PEAKS


@pytest.mark.parametrize("kernel", ["multpath", "centpath"])
def test_pallas_kernel_lowers_for_v5e(kernel, one_chip, no_persistent_cache):
    from repro.kernels.centpath_mm import centpath_matmul_pallas
    from repro.kernels.tropical_mm import multpath_matmul_pallas

    fn = {"multpath": multpath_matmul_pallas,
          "centpath": centpath_matmul_pallas}[kernel]
    ft = _shape(one_chip, N, NB)  # the frontier arrives transposed
    compiled = fn.lower(ft, ft, _shape(one_chip, N, N)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


def test_dense_step_runs_the_kernel_on_v5e(one_chip, no_persistent_cache,
                                           monkeypatch):
    """The jitted dense batch step, with the kernel flag set, embeds the
    Mosaic kernels (on the chip ``ops`` never interprets)."""
    from repro.core.adjacency import DenseAdj
    from repro.core.mfbc import mfbc_batch
    from repro.kernels import ops

    # The described chip is not the default backend, so steer the
    # wrapper to the branch the chip takes.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, nb = 200, 8
    adj = DenseAdj(_shape(one_chip, n, n), 512, True, _shape(one_chip, n, n))
    compiled = mfbc_batch.lower(adj, _shape(one_chip, nb, dtype=jnp.int32),
                                _shape(one_chip, nb, dtype=jnp.bool_)
                                ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_coo_moments_step_compiles_for_v5e(one_chip, no_persistent_cache):
    from repro.core.adjacency import coo_adj_from_graph
    from repro.core.mfbc import mfbc_batch_moments
    from repro.graphs.generators import rmat

    g, _ = rmat(8, 16, seed=0).remove_isolated()
    adj = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, *x.shape, dtype=x.dtype),
        coo_adj_from_graph(g))
    nb = 16
    compiled = mfbc_batch_moments.lower(
        adj, _shape(one_chip, nb, dtype=jnp.int32),
        _shape(one_chip, nb, dtype=jnp.bool_)).compile()
    assert _fits(compiled)


def test_csr_traced_step_compiles_for_v5e_with_scopes(one_chip,
                                                      no_persistent_cache):
    """The exact cell's program (CSR ladder, both traces returned) at a
    small size: it compiles for v5e, and the TPU compiler keeps the named
    scopes in the op metadata a profiler trace reports as ``tf_op``."""
    from repro.core.adjacency import csr_adj_from_graph
    from repro.core.mfbc import mfbc_batch_moments_traced
    from repro.graphs.generators import rmat

    g, _ = rmat(8, 16, seed=0).remove_isolated()
    adj = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, *x.shape, dtype=x.dtype),
        csr_adj_from_graph(g, n_b=16))
    nb = 16
    compiled = mfbc_batch_moments_traced.lower(
        adj, _shape(one_chip, nb, dtype=jnp.int32),
        _shape(one_chip, nb, dtype=jnp.bool_)).compile()
    assert _fits(compiled)
    text = compiled.as_text()
    for scope in ("mfbf/", "mfbr/", "/init/", "/relax.pick/", "/relax.rung0/",
                  "/relax.full_edge/", "/update/", "batch.reduce/"):
        assert scope in text, scope
