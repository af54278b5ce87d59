"""Pallas kernel validation: interpret-mode vs pure-jnp oracles across a
shape/dtype sweep (per-kernel allclose, as required)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

from repro.kernels import ops, ref

SHAPES = [  # (N, d, K)
    (64, 4, 2),
    (256, 24, 30),       # the paper's MNIST setting
    (1000, 11, 15),      # VEHICLE
    (513, 84, 10),       # WADI, non-aligned N
    (100, 38, 10),       # SMD
    (2048, 128, 64),     # aligned everything
    (17, 3, 1),          # degenerate small
]


def make_inputs(rng, n, d, k, dtype=jnp.float32):
    x = jnp.asarray(rng.normal(0, 2, (n, d)), dtype)
    mu = jnp.asarray(rng.normal(0, 2, (k, d)), jnp.float32)
    var = jnp.asarray(rng.uniform(0.05, 3.0, (k, d)), jnp.float32)
    lw = jnp.asarray(np.log(rng.dirichlet(np.ones(k))), jnp.float32)
    return x, mu, var, lw


class TestGMMLogpdf:
    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_matches_ref(self, n, d, k):
        rng = np.random.default_rng(n * 31 + d * 7 + k)
        x, mu, var, lw = make_inputs(rng, n, d, k)
        out = ops.gmm_logpdf(x, mu, var, lw, interpret=True)
        exp = ref.gmm_logpdf_ref(x, mu, var, lw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4)

    def test_no_log_weights(self):
        rng = np.random.default_rng(0)
        x, mu, var, _ = make_inputs(rng, 100, 8, 4)
        out = ops.gmm_logpdf(x, mu, var, None, interpret=True)
        exp = ref.gmm_logpdf_ref(x, mu, var, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4)

    def test_bfloat16_input(self):
        rng = np.random.default_rng(1)
        x, mu, var, lw = make_inputs(rng, 128, 16, 8, dtype=jnp.bfloat16)
        out = ops.gmm_logpdf(x, mu, var, lw, interpret=True)
        exp = ref.gmm_logpdf_ref(x.astype(jnp.float32), mu, var, lw)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=0.05, atol=0.3)

    def test_block_shape_invariance(self):
        rng = np.random.default_rng(2)
        x, mu, var, lw = make_inputs(rng, 512, 24, 30)
        a = ops.gmm_logpdf(x, mu, var, lw, block_n=128, block_k=128,
                           interpret=True)
        b = ops.gmm_logpdf(x, mu, var, lw, block_n=512, block_k=256,
                           interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


class TestEstepStats:
    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_matches_ref(self, n, d, k):
        rng = np.random.default_rng(n * 13 + d + k)
        x, mu, var, lw = make_inputs(rng, n, d, k)
        w = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
        s0, s1, s2, ll = ops.estep_stats(x, mu, var, lw, w, interpret=True)
        e0, e1, e2, el = ref.estep_stats_ref(x, mu, var, lw, w)
        np.testing.assert_allclose(np.asarray(s0), np.asarray(e0), rtol=1e-3,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(e1), rtol=1e-3,
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(e2), rtol=1e-3,
                                   atol=1e-3)
        np.testing.assert_allclose(float(ll), float(el), rtol=1e-4)

    def test_unit_weights_default(self):
        rng = np.random.default_rng(3)
        x, mu, var, lw = make_inputs(rng, 200, 10, 5)
        s0, *_ = ops.estep_stats(x, mu, var, lw, None, interpret=True)
        np.testing.assert_allclose(float(jnp.sum(s0)), 200.0, rtol=1e-4)

    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_prepared_slab_is_bit_identical(self, n, d, k):
        """A slab prepared once gives the kernel the bytes a per-call pad
        gives it: the same statistics, bit for bit."""
        rng = np.random.default_rng(n * 5 + d + k)
        x, mu, var, lw = make_inputs(rng, n, d, k)
        w = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
        per_call = ops.estep_stats(x, mu, var, lw, w, interpret=True)
        slab = ops.estep_stats(ops.prepare(x, w), mu, var, lw,
                               interpret=True)
        for a, b in zip(per_call, slab):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_slab_carries_its_weights(self):
        rng = np.random.default_rng(5)
        x, mu, var, lw = make_inputs(rng, 100, 8, 4)
        slab = ops.prepare(x)
        assert slab.x.shape == (512, 128) and slab.w.shape == (512, 1)
        assert (slab.n, slab.d) == (100, 8)
        np.testing.assert_array_equal(np.asarray(slab.w[:, 0]),
                                      np.r_[np.ones(100), np.zeros(412)])
        with pytest.raises(ValueError, match="carries its weights"):
            ops.estep_stats(slab, mu, var, lw, jnp.ones(100),
                            interpret=True)
        assert ops.slab_bytes(100, 8) == 512 * 256 * 4
        assert ops.slab_bytes(100, 8, weights=False) == 512 * 128 * 4

    def test_multi_block_accumulation(self):
        """Accumulation across sequential grid steps must equal single block."""
        rng = np.random.default_rng(4)
        x, mu, var, lw = make_inputs(rng, 2048, 16, 8)
        a = ops.estep_stats(x, mu, var, lw, block_n=256, interpret=True)
        b = ops.estep_stats(x, mu, var, lw, block_n=2048, interpret=True)
        for u, v in zip(a, b):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       rtol=1e-4, atol=1e-3)


class TestKmeansAssign:
    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_matches_ref(self, n, d, k):
        rng = np.random.default_rng(n + d * 3 + k * 11)
        x, mu, _, _ = make_inputs(rng, n, d, k)
        ia, da = ops.kmeans_assign(x, mu, interpret=True)
        ie, de = ref.kmeans_assign_ref(x, mu)
        assert bool(jnp.all(ia == ie))
        np.testing.assert_allclose(np.asarray(da), np.asarray(de), rtol=1e-4,
                                   atol=1e-4)

    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_prepared_slab_is_bit_identical(self, n, d, k):
        rng = np.random.default_rng(n * 7 + d + k)
        x, mu, _, _ = make_inputs(rng, n, d, k)
        per_call = ops.kmeans_assign(x, mu, interpret=True)
        slab = ops.kmeans_assign(ops.prepare(x), mu, interpret=True)
        for a, b in zip(per_call, slab):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=15, deadline=None)
@given(n=hst.integers(1, 300), d=hst.integers(1, 40), k=hst.integers(1, 33),
       seed=hst.integers(0, 10**5))
def test_logpdf_property_sweep(n, d, k, seed):
    rng = np.random.default_rng(seed)
    x, mu, var, lw = make_inputs(rng, n, d, k)
    out = ops.gmm_logpdf(x, mu, var, lw, interpret=True)
    exp = ref.gmm_logpdf_ref(x, mu, var, lw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-3,
                               atol=1e-3)
