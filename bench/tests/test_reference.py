"""The float64 reference's check of a sample against a mixture."""
import numpy as np
import pytest

from lib import reference


def _mixture(rng, k=40, d=12):
    w = rng.dirichlet(np.full(k, 5.0))
    mu = rng.uniform(0, 1, (k, d))
    mu[1::2] = mu[::2] + 0.01          # near-duplicate pairs overlap
    var = rng.uniform(0.001, 0.01, (k, d))
    return w, mu, var


def _draw(rng, n, w, mu, var):
    comp = rng.choice(len(w), size=n, p=w)
    return (mu[comp] + np.sqrt(var[comp]) * rng.standard_normal(
        (n, mu.shape[1]))).astype(np.float32)


@pytest.mark.parametrize("seed", range(4))
def test_a_sample_of_the_mixture_reads_about_one(seed):
    rng = np.random.default_rng(seed)
    w, mu, var = _mixture(rng)
    x = _draw(rng, 20000, w, mu, var)
    assert reference.sample_misfit(x, w, mu, var) < 1.5


@pytest.mark.parametrize("wrong", ["weights", "means", "variances"])
def test_a_sample_of_another_mixture_reads_high(wrong):
    rng = np.random.default_rng(7)
    w, mu, var = _mixture(rng)
    drawn = {"weights": (np.roll(w, 2), mu, var),
             "means": (w, np.roll(mu, 1, axis=1), var),
             "variances": (w, mu, 2.0 * var)}[wrong]
    x = _draw(rng, 20000, *drawn)
    assert reference.sample_misfit(x, w, mu, var) > 10.0


def test_merged_mixture_weighs_clients_by_size():
    a = (np.array([0.5, 0.5]), np.zeros((2, 3)), np.ones((2, 3)))
    b = (np.array([1.0]), np.ones((1, 3)), np.ones((1, 3)))
    w, mu, var = reference.merged_mixture([a, b], [300, 100])
    assert w == pytest.approx([0.375, 0.375, 0.25])
    assert mu.shape == var.shape == (3, 3)
