"""Parity tests for the backend-dispatching streaming EM engine.

Three equivalence claims, each load-bearing for the hot-path rewiring:
  1. the fused Pallas E-step (interpret mode on CPU) == reference E-step,
     including odd shapes that are not multiples of the kernel tile sizes;
  2. the chunked (lax.scan) E-step == full-batch E-step for any chunk size,
     including chunk sizes that do not divide N;
  3. full training runs (fit_gmm / the streaming GMMEstimator facade /
     fedgengmm / dem_sharded) are backend- and chunking-invariant.
Plus the regression test for train_locals_bic dropping covariance_type.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

from repro.api import DEM, FedEM, FitConfig, GMMEstimator
from repro.core.em import (e_step_stats, e_step_stats_chunked, fit_gmm,
                           init_from_kmeans, resolve_estep_backend)
from repro.core.fedgen import fedgengmm, train_locals, train_locals_bic
from repro.core.kmeans import kmeans, kmeans_multi
from repro.core.gmm import GMM
from repro.core.partition import partition

from conftest import planted_gmm_data

# Deliberately awkward shapes: N, K, d not multiples of the kernel's tile
# sizes (block_n=512, lanes=128), plus degenerate K=1 / d=1.
ODD_SHAPES = [  # (N, d, K)
    (37, 3, 2),
    (129, 5, 7),
    (513, 11, 5),
    (1000, 24, 30),
    (61, 1, 1),
]


def random_diag_gmm(rng, k, d):
    return GMM(jnp.asarray(rng.dirichlet(np.ones(k)), jnp.float32),
               jnp.asarray(rng.normal(0, 2, (k, d)), jnp.float32),
               jnp.asarray(rng.uniform(0.1, 2.0, (k, d)), jnp.float32))


def assert_stats_close(a, b, rtol=1e-4, atol=1e-4):
    for name, u, v in zip(a._fields, a, b):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=rtol,
                                   atol=atol, err_msg=f"field {name}")


class TestBackendResolution:
    def test_full_covariance_always_reference(self):
        assert resolve_estep_backend("fused", is_diagonal=False) == "reference"
        assert resolve_estep_backend("auto", is_diagonal=False) == "reference"

    def test_auto_is_reference_off_tpu(self):
        if jax.default_backend() != "tpu":
            assert resolve_estep_backend("auto", True) == "reference"

    def test_invalid_backend_rejected(self):
        with pytest.raises(ValueError, match="estep_backend"):
            resolve_estep_backend("cuda", True)
        x = jnp.zeros((8, 2), jnp.float32)
        with pytest.raises(ValueError, match="estep_backend"):
            fit_gmm(jax.random.key(0), x, 1, estep_backend="typo")


class TestFusedVsReference:
    @pytest.mark.parametrize("n,d,k", ODD_SHAPES)
    def test_dispatch_parity_odd_shapes(self, n, d, k):
        rng = np.random.default_rng(n * 7 + d * 3 + k)
        gmm = random_diag_gmm(rng, k, d)
        x = jnp.asarray(rng.normal(0, 2, (n, d)), jnp.float32)
        w = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
        ref = e_step_stats(gmm, x, w, estep_backend="reference")
        fused = e_step_stats(gmm, x, w, estep_backend="fused")
        assert_stats_close(ref, fused, rtol=1e-4, atol=1e-4)

    def test_default_weights(self):
        rng = np.random.default_rng(0)
        gmm = random_diag_gmm(rng, 4, 6)
        x = jnp.asarray(rng.normal(0, 2, (321, 6)), jnp.float32)
        ref = e_step_stats(gmm, x, estep_backend="reference")
        fused = e_step_stats(gmm, x, estep_backend="fused")
        assert_stats_close(ref, fused)


class TestChunkedVsFullBatch:
    # includes dividing (250), non-dividing (333, 64), >N (2048) and 1
    @pytest.mark.parametrize("chunk_size", [1, 64, 250, 333, 999, 2048])
    def test_chunk_size_invariance(self, chunk_size):
        rng = np.random.default_rng(1)
        gmm = random_diag_gmm(rng, 5, 7)
        x = jnp.asarray(rng.normal(0, 2, (1000, 7)), jnp.float32)
        w = jnp.asarray(rng.uniform(0, 1, 1000), jnp.float32)
        full = e_step_stats(gmm, x, w, estep_backend="reference")
        chunked = e_step_stats_chunked(gmm, x, w, chunk_size=chunk_size,
                                       estep_backend="reference")
        assert_stats_close(full, chunked, rtol=1e-4, atol=2e-3)

    def test_full_covariance_chunked(self):
        rng = np.random.default_rng(2)
        k, d = 3, 4
        a = rng.normal(0, 1, (k, d, d))
        covs = (a @ np.transpose(a, (0, 2, 1)) + 0.7 * np.eye(d))
        gmm = GMM(jnp.asarray(rng.dirichlet(np.ones(k)), jnp.float32),
                  jnp.asarray(rng.normal(0, 2, (k, d)), jnp.float32),
                  jnp.asarray(covs, jnp.float32))
        x = jnp.asarray(rng.normal(0, 2, (700, d)), jnp.float32)
        full = e_step_stats(gmm, x)
        chunked = e_step_stats_chunked(gmm, x, chunk_size=128)
        assert chunked.s2.shape == (k, d, d)
        assert_stats_close(full, chunked, rtol=1e-4, atol=2e-3)

    def test_chunked_fused_backend(self):
        """Chunked accumulation composes with the fused kernel per chunk."""
        rng = np.random.default_rng(3)
        gmm = random_diag_gmm(rng, 3, 5)
        x = jnp.asarray(rng.normal(0, 2, (450, 5)), jnp.float32)
        full = e_step_stats(gmm, x, estep_backend="reference")
        chunked = e_step_stats_chunked(gmm, x, chunk_size=200,
                                       estep_backend="fused")
        assert_stats_close(full, chunked, rtol=1e-4, atol=2e-3)

    def test_rejects_bad_chunk_size(self):
        rng = np.random.default_rng(4)
        gmm = random_diag_gmm(rng, 2, 3)
        x = jnp.asarray(rng.normal(0, 1, (10, 3)), jnp.float32)
        with pytest.raises(ValueError, match="chunk_size"):
            e_step_stats_chunked(gmm, x, chunk_size=0)

    # width 2 divides the 8-chunk stack, 3 leaves a ragged super-chunk
    @pytest.mark.parametrize("scan_width", [2, 3, 8])
    def test_two_level_scan_matches_width_one(self, scan_width):
        """The 2-level scan (vmapped super-chunks) changes reduction
        *order*, not value: f32-rounding-level agreement with the serial
        width-1 scan, which stays the reproducibility default."""
        rng = np.random.default_rng(5)
        gmm = random_diag_gmm(rng, 5, 7)
        x = jnp.asarray(rng.normal(0, 2, (1000, 7)), jnp.float32)
        w = jnp.asarray(rng.uniform(0, 1, 1000), jnp.float32)
        serial = e_step_stats(gmm, x, w, estep_backend="reference",
                              chunk_size=128)
        wide = e_step_stats(gmm, x, w, estep_backend="reference",
                            chunk_size=128, scan_width=scan_width)
        assert_stats_close(serial, wide, rtol=1e-3, atol=1e-2)


@pytest.mark.slow
class TestEndToEndParity:
    def test_fit_gmm_fused_matches_reference(self, planted):
        x, _, _ = planted
        xj = jnp.asarray(x)
        init = init_from_kmeans(jax.random.key(0), xj, 3)
        ref = fit_gmm(jax.random.key(0), xj, 3, init_gmm=init,
                      estep_backend="reference")
        fused = fit_gmm(jax.random.key(0), xj, 3, init_gmm=init,
                        estep_backend="fused")
        assert abs(float(ref.log_likelihood) - float(fused.log_likelihood)) \
            < 1e-4
        np.testing.assert_allclose(np.asarray(ref.gmm.means),
                                   np.asarray(fused.gmm.means),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("run", ["train_locals", "dem", "fedem_cohort",
                                     "kmeans", "kmeans_multi"])
    def test_prepared_loops_match_reference(self, run):
        """The loops that pad their rows once for the kernels (the local
        fits, the round loop, a sampled cohort's rounds, the Lloyd
        loops) land where the reference backend lands."""
        x, y, _ = planted_gmm_data(np.random.default_rng(11), n=900, d=3,
                                   k=3, spread=6.0, std=0.5,
                                   min_sep_sigma=8.0)
        split = partition(np.random.default_rng(5), x, y, 3, "dirichlet",
                          5.0)
        key = jax.random.key(0)

        def fit(backend):
            if run == "train_locals":
                gmm, ll, _ = train_locals(key, jnp.asarray(split.data),
                                          jnp.asarray(split.mask), 3,
                                          estep_backend=backend)
                return gmm.means, ll
            if run == "dem":
                res = DEM(3, config=FitConfig(backend=backend)).run(
                    split, key=key)
                return res.global_gmm.means, res.log_likelihood
            if run == "fedem_cohort":
                res = FedEM(3, participation=2 / 3, config=FitConfig(
                    backend=backend, max_iter=8)).run(split, key=key)
                return res.global_gmm.means, res.log_likelihood
            fn = kmeans if run == "kmeans" else kmeans_multi
            res = fn(key, jnp.asarray(x), 3, max_iter=20,
                     assign_backend=backend, seed_rows=512)
            return res.centers, res.inertia / x.shape[0]

        ref_means, ref_ll = fit("reference")
        means, ll = fit("fused")
        np.testing.assert_allclose(np.asarray(ll), np.asarray(ref_ll),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(means), np.asarray(ref_means),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("chunk_size", [128, 500, 4096])
    def test_streaming_facade_matches_reference(self, planted, chunk_size):
        x, _, _ = planted
        xj = jnp.asarray(x)
        ref = fit_gmm(jax.random.key(0), xj, 3)
        stream = GMMEstimator(3, chunk_size=chunk_size,
                              backend="reference").fit(
            xj, key=jax.random.key(0)).result_
        assert abs(float(ref.log_likelihood) - float(stream.log_likelihood)) \
            < 1e-4
        np.testing.assert_allclose(np.asarray(ref.gmm.means),
                                   np.asarray(stream.gmm.means),
                                   rtol=1e-3, atol=1e-3)

    def test_streaming_facade_chunk_invariance(self, planted):
        """End-to-end invariance to chunk_size with the chunked init path:
        k-means, label stats and EM all stream, and any two chunkings
        agree up to float-summation reordering."""
        x, _, _ = planted
        xj = jnp.asarray(x)
        a = GMMEstimator(3, chunk_size=128).fit(
            xj, key=jax.random.key(5)).result_
        b = GMMEstimator(3, chunk_size=1024).fit(
            xj, key=jax.random.key(5)).result_
        assert abs(float(a.log_likelihood) - float(b.log_likelihood)) < 1e-4
        np.testing.assert_allclose(np.asarray(a.gmm.means),
                                   np.asarray(b.gmm.means),
                                   rtol=1e-3, atol=1e-3)

    def test_fedgengmm_chunked_runs(self):
        x, y, _ = planted_gmm_data(np.random.default_rng(6), n=900, d=3, k=3,
                                   spread=6.0, std=0.5, min_sep_sigma=8.0)
        split = partition(np.random.default_rng(0), x, y, 3, "dirichlet", 5.0)
        full = fedgengmm(jax.random.key(0), split, k_clients=3, k_global=3,
                         h=30)
        chunked = fedgengmm(jax.random.key(0), split, k_clients=3, k_global=3,
                            h=30, chunk_size=100, estep_backend="reference")
        ll_full = float(full.global_gmm.score(jnp.asarray(x)))
        ll_chunk = float(chunked.global_gmm.score(jnp.asarray(x)))
        assert abs(ll_full - ll_chunk) < 5e-2, (ll_full, ll_chunk)

    def test_dem_chunked_matches(self):
        from repro.core import dem
        x, y, _ = planted_gmm_data(np.random.default_rng(7), n=800, d=3, k=3,
                                   spread=6.0, std=0.5, min_sep_sigma=8.0)
        split = partition(np.random.default_rng(4), x, y, 4, "dirichlet", 1.0)
        full = dem(jax.random.key(0), split, 3, init=3)
        chunked = dem(jax.random.key(0), split, 3, init=3, chunk_size=128,
                      estep_backend="reference")
        assert int(full.n_rounds) == int(chunked.n_rounds)
        np.testing.assert_allclose(np.asarray(full.global_gmm.means),
                                   np.asarray(chunked.global_gmm.means),
                                   rtol=1e-4, atol=1e-4)

    def test_dem_sharded_chunked_matches(self):
        from repro.core.dem import fed_kmeans_centers
        from repro.distributed import dem_sharded
        mesh = jax.make_mesh((1,), ("data",))
        x, y, _ = planted_gmm_data(np.random.default_rng(8), n=800, d=3, k=3,
                                   spread=6.0, std=0.5, min_sep_sigma=8.0)
        split = partition(np.random.default_rng(1), x, y, 4, "dirichlet", 1.0)
        data, mask = jnp.asarray(split.data), jnp.asarray(split.mask)
        centers = fed_kmeans_centers(jax.random.key(1), split, 3)
        g_full, r_full = dem_sharded(mesh, jax.random.key(2), data, mask, 3,
                                     centers)
        g_chunk, r_chunk = dem_sharded(mesh, jax.random.key(2), data, mask, 3,
                                       centers, chunk_size=96)
        assert int(r_full) == int(r_chunk)
        np.testing.assert_allclose(np.asarray(g_full.means),
                                   np.asarray(g_chunk.means),
                                   rtol=1e-4, atol=1e-4)


class TestTrainLocalsBicCovarianceType:
    """Regression: train_locals_bic used to drop covariance_type, silently
    training diagonal local models on the heterogeneous-K path."""

    @pytest.mark.slow
    def test_covariance_type_threaded(self):
        x, y, _ = planted_gmm_data(np.random.default_rng(9), n=600, d=3, k=2,
                                   spread=5.0, std=0.5, min_sep_sigma=8.0)
        split = partition(np.random.default_rng(2), x, y, 2, "dirichlet", 5.0)
        results = train_locals_bic(jax.random.key(0), split, [2],
                                   max_iter=30, covariance_type="full")
        for r in results:
            assert not r.gmm.is_diagonal, "full covariance was dropped"
            assert r.gmm.covs.shape[-1] == r.gmm.covs.shape[-2] == 3

    @pytest.mark.slow
    def test_fedgengmm_full_covariance_locals(self):
        x, y, _ = planted_gmm_data(np.random.default_rng(10), n=600, d=3, k=2,
                                   spread=5.0, std=0.5, min_sep_sigma=8.0)
        split = partition(np.random.default_rng(3), x, y, 2, "dirichlet", 5.0)
        fr = fedgengmm(jax.random.key(0), split, k_candidates=[2], k_global=2,
                       h=30, max_iter=30, covariance_type="full")
        assert all(not g.is_diagonal for g in fr.local_gmms)
        assert not fr.global_gmm.is_diagonal


@settings(max_examples=8, deadline=None)
@given(n=hst.integers(16, 400), k=hst.integers(1, 9),
       chunk=hst.integers(1, 450), seed=hst.integers(0, 10**6))
def test_chunked_equivalence_property(n, k, chunk, seed):
    """Chunk-sum == batch-sum for arbitrary (n, k, chunk_size)."""
    rng = np.random.default_rng(seed)
    gmm = random_diag_gmm(rng, k, 3)
    x = jnp.asarray(rng.normal(0, 2, (n, 3)), jnp.float32)
    full = e_step_stats(gmm, x, estep_backend="reference")
    chunked = e_step_stats_chunked(gmm, x, chunk_size=chunk,
                                   estep_backend="reference")
    assert_stats_close(full, chunked, rtol=1e-3, atol=2e-3)
