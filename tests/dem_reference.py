"""A plain reference of distributed EM for the tests: every client's rows
pooled into one array and EM run over them in float32 ``jax.numpy``, with
no kernels, ``vmap``, ``shard_map``, padding or client sums, and every
contraction at ``Precision.HIGHEST``.

DEM sums the clients' sufficient statistics before each M-step, and the
statistics are additive in the rows, so a DEM round over any grouping of
the rows into clients and shards is one EM iteration over the pooled rows;
the two differ only in the order of float32 sums. The E-step here takes
the textbook ``(x - mu)^2 / var`` form, where the program takes the
matmul identity (DESIGN.md §3).
"""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def pooled_rows(split):
    """The real rows of every client, one after another: (N, d)."""
    return jnp.concatenate([jnp.asarray(split.data[c, :int(n)])
                            for c, n in enumerate(split.sizes)])


def em_rounds(x, weights, means, covs, rounds: int, reg_covar=1e-6):
    """``rounds`` EM iterations of a diagonal mixture over the rows ``x``
    -> (weights, means, covs, the average log-likelihood of the model the
    last E-step scored), as a DEM round reports it."""
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    ll = None
    for _ in range(rounds):
        diff = x[:, None, :] - means[None, :, :]                 # (N, K, d)
        lp = (-0.5 * (jnp.sum(diff * diff / covs[None], axis=-1)
                      + jnp.sum(jnp.log(covs), axis=-1)[None]
                      + d * math.log(2.0 * math.pi))
              + jnp.log(weights)[None])
        log_norm = jax.scipy.special.logsumexp(lp, axis=1)
        resp = jnp.exp(lp - log_norm[:, None])
        s0 = jnp.sum(resp, axis=0)
        s1 = jnp.matmul(resp.T, x, precision=HIGHEST)
        s2 = jnp.matmul(resp.T, x * x, precision=HIGHEST)
        ll = jnp.sum(log_norm) / n
        weights = s0 / n
        means = s1 / s0[:, None]
        covs = jnp.maximum(s2 / s0[:, None] - means * means, 0.0) + reg_covar
    return weights, means, covs, ll
