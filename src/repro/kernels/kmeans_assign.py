"""Pallas TPU kernel: k-means assignment (nearest center + squared distance).

Same matmul identity as the GMM kernels: ||x - c||^2 = ||x||^2 - 2 x.c +
||c||^2; the centers panel (d, K) stays VMEM-resident, data tiles stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_N = 512


def _assign_kernel(x_ref, ct_ref, c2_ref, idx_ref, dist_ref):
    x = x_ref[...].astype(jnp.float32)            # (bn, d)
    ct = ct_ref[...].astype(jnp.float32)          # (d, K)
    c2 = c2_ref[...].astype(jnp.float32)          # (1, K) (+inf on padding)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)    # (bn, 1)
    xc = jnp.dot(x, ct, preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)  # full f32, as in gmm
    d2 = x2 - 2.0 * xc + c2
    d2 = jnp.maximum(d2, 0.0)
    idx_ref[...] = jnp.argmin(d2, axis=1, keepdims=True).astype(jnp.int32)
    dist_ref[...] = jnp.min(d2, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_pallas(x: jax.Array, ct: jax.Array, c2: jax.Array, *,
                         block_n: int = DEFAULT_BLOCK_N,
                         interpret: bool = False):
    """x (N, d), ct (d, K) transposed centers, c2 (1, K) squared norms
    (+1e30 on padded columns). Returns (assign (N,1) int32, d2min (N,1))."""
    n, d = x.shape
    k = ct.shape[1]
    assert n % block_n == 0
    return pl.pallas_call(
        _assign_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, ct, c2)
