"""Pallas TPU kernel: fused EM E-step sufficient statistics.

Fuses  log-pdf -> per-row softmax (responsibilities) -> the three weighted
reductions  (s0, s1, s2)  plus the total log-likelihood into one pass over
the data. The (N, K) responsibility matrix never exists in HBM — the
flash-attention trick applied to EM. K (number of mixture components) is
small (<= a few hundred), so the K axis and the (K, d) accumulators stay
VMEM-resident while (bn, d) data tiles stream through.

The grid runs over (clients, row blocks), sequential over the row blocks,
so accumulation into a client's output refs (initialized at its first
block) is race-free. Each client computes only its first ``blocks[c]``
row blocks, a scalar-prefetch operand: past them its rows carry no weight
and would add exactly zero, so those grid steps neither compute nor fetch
(their row index is held at the last computed block, and an unchanged
block index issues no new DMA). A client of 0 blocks returns zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_N = 512


def _estep_kernel(blocks_ref, x_ref, w_ref, a_ref, b_ref, c_ref,
                  s0_ref, s1_ref, s2_ref, ll_ref):
    client, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        s0_ref[...] = jnp.zeros_like(s0_ref)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    @pl.when(i < blocks_ref[client])
    def _accumulate():
        # full float32 dots: a bfloat16 pass would move the log densities
        # by whole nats, since the identity's large terms cancel
        # (repro.core.gmm)
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
        s0_ref[...] += jnp.sum(resp, axis=0, keepdims=True)        # (1, K)
        s1_ref[...] += dot(resp.T, x)
        s2_ref[...] += dot(resp.T, xx)
        ll_ref[...] += jnp.sum(log_norm * w, keepdims=True)


def _spec(shape, per_client: bool, index):
    """BlockSpec of an operand whose block is ``shape``: with a leading
    client axis (squeezed, indexed by the grid's client) where
    ``per_client``, else the one block shared by every client."""
    if per_client:
        return pl.BlockSpec((pl.squeezed, *shape),
                            lambda c, i, blocks: (c, *index(c, i, blocks)))
    return pl.BlockSpec(shape, lambda c, i, blocks: index(c, i, blocks))


@functools.partial(jax.jit, static_argnames=("per_client", "block_n",
                                             "interpret"))
def estep_stats_pallas(blocks: jax.Array, x: jax.Array, w: jax.Array,
                       a: jax.Array, b: jax.Array, c: jax.Array, *,
                       per_client: tuple = (True,) * 5,
                       block_n: int = DEFAULT_BLOCK_N,
                       interpret: bool = False):
    """Raw fused kernel (padded shapes) over C clients.

    ``blocks`` (C,) int32: the row blocks each client computes. The
    operands x (N, d), w (N, 1) sample weights (0 on padded rows), a
    (d, K), b (d, K) and c (1, K) (c = -1e30 on padded K columns) each
    carry a leading client axis where ``per_client`` says so, and are
    shared by every client where not.
    Returns (s0 (C,1,K), s1 (C,K,d), s2 (C,K,d), loglik (C,1,1)), all
    float32.
    """
    n_clients = blocks.shape[0]
    n, d = x.shape[-2:]
    k = a.shape[-1]
    assert n % block_n == 0, (n, block_n)

    def rows(c, i, blocks):
        return jnp.minimum(i, jnp.maximum(blocks[c] - 1, 0)), 0

    def whole(c, i, blocks):
        return 0, 0

    x_pc, w_pc, a_pc, b_pc, c_pc = per_client
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_clients, n // block_n),
        in_specs=[
            _spec((block_n, d), x_pc, rows),
            _spec((block_n, 1), w_pc, rows),
            _spec((d, k), a_pc, whole),
            _spec((d, k), b_pc, whole),
            _spec((1, k), c_pc, whole),
        ],
        out_specs=[
            _spec((1, k), True, whole),
            _spec((k, d), True, whole),
            _spec((k, d), True, whole),
            _spec((1, 1), True, whole),
        ],
    )
    return pl.pallas_call(
        _estep_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_clients, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((n_clients, k, d), jnp.float32),
            jax.ShapeDtypeStruct((n_clients, k, d), jnp.float32),
            jax.ShapeDtypeStruct((n_clients, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(blocks, x, w, a, b, c)
