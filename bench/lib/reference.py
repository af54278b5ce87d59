"""The plain float64 numpy reference. It imports nothing of ``repro``.

``ref_weighted_logpdf``, ``ref_logsumexp`` and ``identity_error_bound``
are copied from ``chip_smoke.py``. ``em_step`` is one EM iteration of a
diagonal Gaussian mixture in float64, by the textbook E- and M-step; the
fit checks judge a returned model by how far that step moves it.
"""
from __future__ import annotations

import numpy as np

U32 = 2.0 ** -24                     # unit roundoff of float32
LOG_2PI = float(np.log(2.0 * np.pi))


def params64(weights, means, variances):
    return (np.asarray(weights, np.float64), np.asarray(means, np.float64),
            np.asarray(variances, np.float64))


def ref_weighted_logpdf(x, weights, means, variances):
    """(N, d) -> (N, K): log w_k + log N(x | mu_k, diag(var_k)) in float64
    by the direct (x - mu)^2 / var form (the code under test uses the
    matmul identity instead)."""
    w, mu, var = params64(weights, means, variances)
    x = np.asarray(x, np.float64)
    out = np.empty((x.shape[0], mu.shape[0]))
    for j in range(mu.shape[0]):
        diff = x - mu[j]
        out[:, j] = (-0.5 * (np.sum(diff * diff / var[j], axis=1)
                             + np.sum(np.log(var[j]))
                             + x.shape[1] * LOG_2PI) + np.log(w[j]))
    return out


def ref_logsumexp(lp):
    m = lp.max(axis=1, keepdims=True)
    return m[:, 0] + np.log(np.exp(lp - m).sum(axis=1))


def identity_error_bound(x, weights, means, variances):
    """Per-row bound on the float32 rounding error of a mixture component
    log density computed by the matmul identity
    ``x^2 @ (-1/(2 var)) + x @ (mu/var) + c``.

    An inner product of n float32 terms is off by at most
    ``gamma_n * sum|terms|``, ``gamma_n = n u / (1 - n u)`` (Higham,
    Accuracy and Stability of Numerical Algorithms, 2nd ed., eq. 3.5).
    The identity sums 2d products plus a constant that is itself a sum
    of d terms; n = 2d + 8 also covers the roundings of x^2, 1/var and
    mu/var."""
    w, mu, var = params64(weights, means, variances)
    x = np.asarray(x, np.float64)
    d = x.shape[1]
    mag = ((x * x) @ (0.5 / var).T + np.abs(x) @ np.abs(mu / var).T
           + (0.5 * np.sum(mu * mu / var + np.abs(np.log(var)), axis=1)
              + 0.5 * d * LOG_2PI + np.abs(np.log(w)))[None, :])
    n = 2 * d + 8
    return n * U32 / (1 - n * U32) * mag.max(axis=1)


def score_tolerance(x, params, ref):
    """Per-row float32 error bound on a mixture log density: the identity
    bound plus 16 ulps of the result for the logsumexp's exp, sum and
    log. The served-score check reads errors as a share of it."""
    return identity_error_bound(x, *params) + 16 * U32 * np.maximum(
        1.0, np.abs(ref))


def _weighted_logpdf_fast(x, w, mu, var):
    """The same density as :func:`ref_weighted_logpdf` through float64
    matrix products (the expansion loses nothing that matters at float64:
    its terms are at most ~1e4, so the cancellation costs ~1e-12)."""
    inv = 1.0 / var
    quad = (x * x) @ inv.T - 2.0 * x @ (mu * inv).T \
        + np.sum(mu * mu * inv, axis=1)[None, :]
    return -0.5 * (quad + np.sum(np.log(var), axis=1)[None, :]
                   + x.shape[1] * LOG_2PI) + np.log(w)[None, :]


def em_step(x, weights, means, variances, reg_covar=1e-6, block=65536):
    """One float64 EM iteration over the rows ``x`` -> ((w', mu', var'),
    the average log-likelihood of the given model)."""
    w, mu, var = params64(weights, means, variances)
    k, d = mu.shape
    s0 = np.zeros(k)
    s1 = np.zeros((k, d))
    s2 = np.zeros((k, d))
    ll = 0.0
    n = x.shape[0]
    for i in range(0, n, block):
        xb = np.asarray(x[i:i + block], np.float64)
        lp = _weighted_logpdf_fast(xb, w, mu, var)
        ln = ref_logsumexp(lp)
        r = np.exp(lp - ln[:, None])
        s0 += r.sum(axis=0)
        s1 += r.T @ xb
        s2 += r.T @ (xb * xb)
        ll += ln.sum()
    w_new = s0 / n
    mu_new = s1 / np.maximum(s0, 1e-10)[:, None]
    var_new = np.maximum(s2 / np.maximum(s0, 1e-10)[:, None]
                         - mu_new * mu_new, 0.0) + reg_covar
    return (w_new, mu_new, var_new), ll / n


def avg_loglik(x, weights, means, variances, block=65536) -> float:
    """Average float64 log-likelihood of the rows under a model."""
    w, mu, var = params64(weights, means, variances)
    total = 0.0
    for i in range(0, x.shape[0], block):
        xb = np.asarray(x[i:i + block], np.float64)
        total += ref_logsumexp(_weighted_logpdf_fast(xb, w, mu, var)).sum()
    return total / x.shape[0]


def merged_mixture(local_params, sizes):
    """FedGenGMM's server merge in float64: every client's components,
    each weight scaled by the client's share of all training rows."""
    sizes = np.asarray(sizes, np.float64)
    w = np.concatenate([np.asarray(p[0], np.float64) * s
                        for p, s in zip(local_params, sizes)])
    mu = np.concatenate([np.asarray(p[1], np.float64) for p in local_params])
    var = np.concatenate([np.asarray(p[2], np.float64) for p in local_params])
    return w / w.sum(), mu, var


def sample_misfit(x, weights, means, variances, min_rows=20.0, block=65536):
    """How far rows said to be drawn from a diagonal Gaussian mixture
    stray from it, as the largest of three mean squared z-scores.

    For rows drawn from the mixture and r_j their responsibilities under
    it, E[r_j] = w_j, E[r_j (x - mu_j)] = 0 and E[r_j ((x - mu_j)^2 -
    var_j)] = 0, however much the components overlap; the variance of
    each term is at most w_j(1 - w_j), w_j var_j and 2 w_j var_j^2. So
    each sum over the n rows, divided by the square root of n times that
    bound, has a mean square of at most 1: for a component's count, for
    its mean in each feature, and for its variance in each feature. Rows
    drawn with other weights, from other components, or with other
    spreads read well above 1. Components the rows barely reach
    (n w_j < ``min_rows``) are left out: a count of a few has no normal
    tail."""
    w, mu, var = params64(weights, means, variances)
    n = x.shape[0]
    k, d = mu.shape
    s0 = np.zeros(k)
    s1 = np.zeros((k, d))
    s2 = np.zeros((k, d))
    for i in range(0, n, block):
        xb = np.asarray(x[i:i + block], np.float64)
        lp = _weighted_logpdf_fast(xb, w, mu, var)
        r = np.exp(lp - ref_logsumexp(lp)[:, None])
        s0 += r.sum(axis=0)
        s1 += r.T @ xb
        s2 += r.T @ (xb * xb)
    keep = n * w >= min_rows
    nw = n * w[keep][:, None]
    mu, var = mu[keep], var[keep]
    s0, s1, s2 = s0[keep][:, None], s1[keep], s2[keep]
    z_count = (s0 - nw) / np.sqrt(nw * (1.0 - nw / n))
    dev1 = s1 - s0 * mu                                   # sum r (x - mu)
    dev2 = s2 - 2.0 * mu * s1 + s0 * mu * mu              # sum r (x - mu)^2
    z_mean = dev1 / np.sqrt(nw * var)
    z_var = (dev2 - s0 * var) / (var * np.sqrt(2.0 * nw))
    return float(max(np.mean(z * z) for z in (z_count, z_mean, z_var)))


def step_gain(x, weights, means, variances, reg_covar=1e-6):
    """How much one float64 EM step from a returned model raises the
    average log-likelihood of its rows (nats per row), and that model's
    own average log-likelihood. EM stops once a step gains less than its
    tolerance, and the gains shrink as it converges, so a converged fit
    reads below the tolerance; a model that EM has not brought to a fixed
    point on these rows (an initial model, a fit to other rows, a model
    altered after the fit) reads above it."""
    new, ll = em_step(x, weights, means, variances, reg_covar)
    return avg_loglik(x, *new) - ll, ll
