"""Gaussian Mixture Model primitives.

A GMM is a pytree of (weights, means, covs):
  weights : (K,)        mixing weights, sum to 1
  means   : (K, d)
  covs    : (K, d)      diagonal covariance (variances), or
            (K, d, d)   full covariance

All log-density math uses the matmul identity (see DESIGN.md §3/§5) so the
E-step maps onto the MXU on TPU; the Pallas kernel in
``repro.kernels.gmm_logpdf`` implements the same contraction with explicit
VMEM tiling, and this module is its reference semantics.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

LOG_2PI = 1.8378770664093453

# Every float32 contraction of the GMM path runs at full float32 precision.
# A TPU's default f32 matmul rounds its inputs to bfloat16, and the matmul
# identity's large terms cancel: at WADI's spread (rows near 0.5, class
# spread ~0.1) x^2/var is ~25 per feature, so one bf16 pass moves a log
# density by whole nats. The CPU computes f32 dots in f32 either way.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GMM:
    """Gaussian mixture parameters (a pytree)."""

    weights: jax.Array  # (K,)
    means: jax.Array    # (K, d)
    covs: jax.Array     # (K, d) diagonal variances or (K, d, d) full

    def tree_flatten(self):
        return (self.weights, self.means, self.covs), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_components(self) -> int:
        return self.weights.shape[0]

    @property
    def n_features(self) -> int:
        return self.means.shape[1]

    @property
    def is_diagonal(self) -> bool:
        return self.covs.ndim == 2

    # ------------------------------------------------------------------
    def component_log_prob(self, x: jax.Array) -> jax.Array:
        """Per-component Gaussian log density. x: (N, d) -> (N, K)."""
        if self.is_diagonal:
            return _diag_component_log_prob(x, self.means, self.covs)
        return _full_component_log_prob(x, self.means, self.covs)

    def log_prob(self, x: jax.Array) -> jax.Array:
        """Mixture log density. x: (N, d) -> (N,)."""
        lp = self.component_log_prob(x) + jnp.log(self.weights)[None, :]
        return jax.scipy.special.logsumexp(lp, axis=1)

    def responsibilities(self, x: jax.Array) -> jax.Array:
        """Posterior component responsibilities. x: (N, d) -> (N, K)."""
        lp = self.component_log_prob(x) + jnp.log(self.weights)[None, :]
        return jax.nn.softmax(lp, axis=1)

    def score(self, x: jax.Array, sample_weight: Optional[jax.Array] = None) -> jax.Array:
        """Average log-likelihood (the paper's fitness score, Eq. 2)."""
        lp = self.log_prob(x)
        if sample_weight is None:
            return jnp.mean(lp)
        w = sample_weight
        return jnp.sum(lp * w) / jnp.maximum(jnp.sum(w), 1e-12)

    def sample(self, key: jax.Array, n: int) -> jax.Array:
        """Draw n samples from the mixture -> (n, d)."""
        k_comp, k_noise = jax.random.split(key)
        comp = jax.random.categorical(k_comp, jnp.log(self.weights), shape=(n,))
        mu = self.means[comp]  # (n, d)
        if self.is_diagonal:
            std = jnp.sqrt(self.covs[comp])
            eps = jax.random.normal(k_noise, mu.shape, dtype=mu.dtype)
            return mu + std * eps
        chol = jnp.linalg.cholesky(self.covs)[comp]  # (n, d, d)
        eps = jax.random.normal(k_noise, mu.shape, dtype=mu.dtype)
        return mu + jnp.einsum("nij,nj->ni", chol, eps,
                               precision=MATMUL_PRECISION)

    # ------------------------------------------------------------------
    def n_free_params(self) -> int:
        """Number of free parameters (for BIC)."""
        k, d = self.means.shape
        cov_params = k * d if self.is_diagonal else k * d * (d + 1) // 2
        return (k - 1) + k * d + cov_params

    def bic(self, x: jax.Array, sample_weight: Optional[jax.Array] = None) -> jax.Array:
        """Bayesian Information Criterion (lower is better)."""
        if sample_weight is None:
            n = x.shape[0]
            total_ll = jnp.sum(self.log_prob(x))
        else:
            n = jnp.sum(sample_weight)
            total_ll = jnp.sum(self.log_prob(x) * sample_weight)
        return self.n_free_params() * jnp.log(n) - 2.0 * total_ll


# ----------------------------------------------------------------------
# Log-density kernels (pure jnp; mirrored by repro/kernels/gmm_logpdf)
# ----------------------------------------------------------------------

def _diag_component_log_prob(x: jax.Array, means: jax.Array, variances: jax.Array) -> jax.Array:
    """log N(x | mu_k, diag(var_k)) for all k, via two matmuls.

    -2 log N = (x - mu)^T var^{-1} (x - mu) + sum(log var) + d log 2pi
             = x^2 @ (1/var)^T  - 2 x @ (mu/var)^T + sum(mu^2/var)
               + sum(log var) + d log 2pi
    """
    d = x.shape[-1]
    inv_var = 1.0 / variances                      # (K, d)
    a = jnp.matmul(x * x, inv_var.T, precision=MATMUL_PRECISION)  # (N, K)
    b = jnp.matmul(x, (means * inv_var).T,
                   precision=MATMUL_PRECISION)                   # (N, K)
    c = jnp.sum(means * means * inv_var + jnp.log(variances), axis=-1)  # (K,)
    return -0.5 * (a - 2.0 * b + c[None, :] + d * LOG_2PI)


def _full_component_log_prob(x: jax.Array, means: jax.Array, covs: jax.Array) -> jax.Array:
    """log N(x | mu_k, Sigma_k) for all k via Cholesky. x: (N,d) -> (N,K)."""
    d = x.shape[-1]
    chol = jnp.linalg.cholesky(covs)               # (K, d, d)
    diff = x[:, None, :] - means[None, :, :]       # (N, K, d)
    # Solve L y = diff for each component.
    y = jax.vmap(
        lambda L, v: jax.scipy.linalg.solve_triangular(L, v.T, lower=True).T,
        in_axes=(0, 1), out_axes=1,
    )(chol, diff)                                  # (N, K, d)
    maha = jnp.sum(y * y, axis=-1)                 # (N, K)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol, axis1=-2, axis2=-1)), axis=-1)  # (K,)
    return -0.5 * (maha + logdet[None, :] + d * LOG_2PI)


# ----------------------------------------------------------------------
# Construction / merging helpers
# ----------------------------------------------------------------------

def merge_gmms(gmms: list[GMM], dataset_sizes: jax.Array) -> GMM:
    """FedGenGMM server-side merge (Algorithm 4.1 lines 21-29).

    Re-weights each client's component weights by |D_c| / |D| and
    concatenates all components into a single mixture, then normalizes.
    Clients may have different numbers of components.
    """
    sizes = jnp.asarray(dataset_sizes, dtype=jnp.float32)
    total = jnp.sum(sizes)
    ws, mus, covs = [], [], []
    for g, s in zip(gmms, sizes):
        ws.append(g.weights * (s / total))
        mus.append(g.means)
        covs.append(g.covs)
    w = jnp.concatenate(ws)
    w = w / jnp.sum(w)
    return GMM(w, jnp.concatenate(mus, axis=0), jnp.concatenate(covs, axis=0))


def merge_gmms_stacked(weights: jax.Array, means: jax.Array, covs: jax.Array,
                       dataset_sizes: jax.Array) -> GMM:
    """Vectorized merge for stacked client params (C, K, ...) — the form the
    one-shot all_gather produces in the distributed runtime."""
    sizes = jnp.asarray(dataset_sizes, dtype=weights.dtype)
    w = weights * (sizes / jnp.sum(sizes))[:, None]       # (C, K)
    w = w.reshape(-1)
    w = w / jnp.sum(w)
    k = means.shape[0] * means.shape[1]
    return GMM(w, means.reshape(k, -1), covs.reshape((k,) + covs.shape[2:]))
