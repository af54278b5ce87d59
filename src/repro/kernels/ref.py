"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

LOG_2PI = 1.8378770664093453
HIGHEST = jax.lax.Precision.HIGHEST  # float32 dots on every backend


def gmm_logpdf_ref(x: jax.Array, means: jax.Array, variances: jax.Array,
                   log_weights: jax.Array | None = None) -> jax.Array:
    """Per-component diagonal-Gaussian log density. (N,d),(K,d),(K,d)->(N,K).

    If log_weights is given, returns log(w_k N(x|...)) (the E-step numerator).
    """
    d = x.shape[-1]
    inv_var = 1.0 / variances
    a = jnp.matmul(x * x, inv_var.T, precision=HIGHEST)
    b = jnp.matmul(x, (means * inv_var).T, precision=HIGHEST)
    c = jnp.sum(means * means * inv_var + jnp.log(variances), axis=-1)
    out = -0.5 * (a - 2.0 * b + c[None, :] + d * LOG_2PI)
    if log_weights is not None:
        out = out + log_weights[None, :]
    return out


def estep_stats_ref(x: jax.Array, means: jax.Array, variances: jax.Array,
                    log_weights: jax.Array,
                    sample_weight: jax.Array | None = None):
    """Fused E-step sufficient statistics (diagonal covariance).

    Returns (s0 (K,), s1 (K,d), s2 (K,d), loglik ()).
    """
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
    lp = gmm_logpdf_ref(x, means, variances, log_weights)       # (N, K)
    log_norm = jax.scipy.special.logsumexp(lp, axis=1)           # (N,)
    resp = jnp.exp(lp - log_norm[:, None]) * w[:, None]          # (N, K)
    s0 = jnp.sum(resp, axis=0)
    s1 = jnp.matmul(resp.T, x, precision=HIGHEST)
    s2 = jnp.matmul(resp.T, x * x, precision=HIGHEST)
    loglik = jnp.sum(log_norm * w)
    return s0, s1, s2, loglik


def kmeans_assign_ref(x: jax.Array, centers: jax.Array):
    """Squared distances + argmin assignment. (N,d),(K,d) -> ((N,), (N,))."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=1)[None, :]
    xc = jnp.matmul(x, centers.T, precision=HIGHEST)
    d2 = jnp.maximum(x2 - 2.0 * xc + c2, 0.0)
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)
