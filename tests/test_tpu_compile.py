"""Ahead-of-time compiles of the main path's Pallas kernels for a described
TPU v5e, at the widths the paper deployments run.

Nothing runs: each kernel is lowered for a chip that is described, not
attached, and compiled by the TPU compiler installed beside JAX. That
catches what interpret mode cannot (tile alignment, VMEM limits, a kernel
the compiler refuses) at no chip time. Each compiled program must hold the
Mosaic kernel itself (``tpu_custom_call``), not an interpret-mode loop.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, F32, sharding=sharding)


def _assert_kernel(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,d,k", [(65536, 84, 10), (65536, 24, 30)])
def test_estep_stats_compiles(one_chip, n, d, k):
    _assert_kernel(functools.partial(ops.estep_stats, interpret=False),
                   _spec(one_chip, n, d), _spec(one_chip, k, d),
                   _spec(one_chip, k, d), _spec(one_chip, k),
                   _spec(one_chip, n))


def test_gmm_logpdf_compiles(one_chip):
    n, d, k = 65536, 84, 10
    _assert_kernel(functools.partial(ops.gmm_logpdf, interpret=False),
                   _spec(one_chip, n, d), _spec(one_chip, k, d),
                   _spec(one_chip, k, d), _spec(one_chip, k))


def test_kmeans_assign_compiles(one_chip):
    n, d, k = 65536, 84, 10
    _assert_kernel(functools.partial(ops.kmeans_assign, interpret=False),
                   _spec(one_chip, n, d), _spec(one_chip, k, d))


def test_vmapped_estep_stats_compiles(one_chip):
    """The fused E-step as the split-client backend runs it: vmapped over
    a (clients, rows, d) slab, one shared model."""
    c, n, d, k = 20, 65536, 84, 10

    def clients(x, w, means, variances, log_weights):
        return jax.vmap(lambda xc, wc: ops.estep_stats(
            xc, means, variances, log_weights, wc, interpret=False))(x, w)

    _assert_kernel(clients, _spec(one_chip, c, n, d), _spec(one_chip, c, n),
                   _spec(one_chip, k, d), _spec(one_chip, k, d),
                   _spec(one_chip, k))
