"""Pallas TPU kernel: batched diagonal-Gaussian mixture log densities.

The E-step hot spot. Uses the matmul identity (DESIGN.md §3): with
``A = -0.5 / var`` (d, K), ``B = mu / var`` (d, K) and a per-component
constant row ``c`` (1, K),

    logpdf[n, k] = (x[n]*x[n]) @ A[:, k] + x[n] @ B[:, k] + c[k]

Both contractions hit the MXU. The kernel streams (bn, d) tiles of x
through VMEM, keeps the (d, bk) parameter panels resident, and squares x
in-register so x**2 never round-trips through HBM (that is the win over the
naive XLA lowering, which materializes x*x at HBM).

Grid: (N/bn, K/bk); the feature dim d is small for GMM workloads (<= a few
hundred after the paper's PCA) and lives whole in VMEM, padded to the
128-lane boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 128


def _logpdf_kernel(x_ref, a_ref, b_ref, c_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)           # (bn, d)
    a = a_ref[...].astype(jnp.float32)           # (d, bk)
    b = b_ref[...].astype(jnp.float32)           # (d, bk)
    # full float32 dots: a bfloat16 pass would move the log densities by
    # whole nats, since the identity's large terms cancel (repro.core.gmm)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    acc = dot(x * x, a)
    acc += dot(x, b)
    out_ref[...] = (acc + c_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def gmm_logpdf_pallas(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
                      *, block_n: int = DEFAULT_BLOCK_N,
                      block_k: int = DEFAULT_BLOCK_K,
                      interpret: bool = False) -> jax.Array:
    """Raw tiled kernel. Shapes must already be padded:
    x (N, d), a (d, K), b (d, K), c (1, K) with N % block_n == 0,
    K % block_k == 0, d % 128 == 0. Returns (N, K) float32.
    """
    n, d = x.shape
    k = a.shape[1]
    assert n % block_n == 0 and k % block_k == 0, (n, k, block_n, block_k)
    grid = (n // block_n, k // block_k)
    return pl.pallas_call(
        _logpdf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((d, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_k), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, a, b, c)
