"""Pallas TPU kernel: fused EM E-step sufficient statistics.

Fuses  log-pdf -> per-row softmax (responsibilities) -> the three weighted
reductions  (s0, s1, s2)  plus the total log-likelihood into one pass over
the data. The (N, K) responsibility matrix never exists in HBM — the
flash-attention trick applied to EM. K (number of mixture components) is
small (<= a few hundred), so the K axis and the (K, d) accumulators stay
VMEM-resident while (bn, d) data tiles stream through.

The TPU grid is sequential over the N tiles, so accumulation into the
output refs (initialized at program_id 0) is race-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_N = 512


def _estep_kernel(x_ref, w_ref, a_ref, b_ref, c_ref,
                  s0_ref, s1_ref, s2_ref, ll_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        s0_ref[...] = jnp.zeros_like(s0_ref)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    # full float32 dots: a bfloat16 pass would move the log densities by
    # whole nats, since the identity's large terms cancel (repro.core.gmm)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    x = x_ref[...].astype(jnp.float32)            # (bn, d)
    w = w_ref[...].astype(jnp.float32)            # (bn, 1)
    xx = x * x
    lp = dot(xx, a_ref[...].astype(jnp.float32))
    lp += dot(x, b_ref[...].astype(jnp.float32))
    lp += c_ref[...].astype(jnp.float32)          # (bn, K)
    m = jnp.max(lp, axis=1, keepdims=True)        # (bn, 1)
    p = jnp.exp(lp - m)
    denom = jnp.sum(p, axis=1, keepdims=True)     # (bn, 1)
    log_norm = m + jnp.log(denom)                 # (bn, 1)
    resp = (p / denom) * w                        # (bn, K)
    s0_ref[...] += jnp.sum(resp, axis=0, keepdims=True)            # (1, K)
    s1_ref[...] += dot(resp.T, x)
    s2_ref[...] += dot(resp.T, xx)
    ll_ref[...] += jnp.sum(log_norm * w, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def estep_stats_pallas(x: jax.Array, w: jax.Array, a: jax.Array,
                       b: jax.Array, c: jax.Array, *,
                       block_n: int = DEFAULT_BLOCK_N,
                       interpret: bool = False):
    """Raw fused kernel (padded shapes).

    x (N, d), w (N, 1) sample weights (0 on padded rows), a (d, K),
    b (d, K), c (1, K) with c = -1e30 on padded K columns.
    Returns (s0 (1,K), s1 (K,d), s2 (K,d), loglik (1,1)), all float32.
    """
    n, d = x.shape
    k = a.shape[1]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        _estep_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((d, k), lambda i: (0, 0)),
            pl.BlockSpec((d, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, a, b, c)
