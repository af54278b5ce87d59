"""DEM baseline tests: distributed stats aggregation == centralized EM,
all three inits converge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dem, e_step_stats, fit_gmm, partition
from repro.core.dem import (fed_kmeans_centers, max_separated_centers,
                            pilot_subset_centers)
from repro.core.em import init_from_means
from conftest import planted_gmm_data

# end-to-end fits: multi-second EM training loops on CPU
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(21)
    x, y, _ = planted_gmm_data(rng, n=2400, d=4, k=3, spread=5.0, std=0.5)
    split = partition(np.random.default_rng(0), x, y, 6, "dirichlet", 0.5)
    return x, y, split


class TestDEMEquivalence:
    def test_distributed_estep_equals_centralized(self, setup):
        """sum of per-client sufficient stats == stats on the union —
        the correctness core of DEM (and of the sharded runtime psum)."""
        x, y, split = setup
        g = init_from_means(max_separated_centers(jax.random.key(0), 3, 4),
                            jnp.asarray(x))
        per = [e_step_stats(g, jnp.asarray(split.data[c]),
                            jnp.asarray(split.mask[c]))
               for c in range(split.data.shape[0])]
        agg = jax.tree.map(lambda *s: sum(s), *per)
        cen = e_step_stats(g, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(agg.s0), np.asarray(cen.s0),
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(agg.s1), np.asarray(cen.s1),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(float(agg.loglik), float(cen.loglik),
                                   rtol=1e-4)

    def test_dem_matches_centralized_fit(self, setup):
        x, y, split = setup
        dr = dem(jax.random.key(0), split, 3, init=3)
        bench = fit_gmm(jax.random.key(1), jnp.asarray(x), 3)
        ll_dem = float(dr.global_gmm.score(jnp.asarray(x)))
        ll_cen = float(bench.gmm.score(jnp.asarray(x)))
        assert ll_dem > ll_cen - 0.3, (ll_dem, ll_cen)


class TestInits:
    @pytest.mark.parametrize("init", [1, 2, 3])
    def test_all_inits_converge(self, setup, init):
        x, y, split = setup
        dr = dem(jax.random.key(init), split, 3, init=init)
        assert bool(dr.converged)
        assert bool(jnp.all(jnp.isfinite(dr.global_gmm.means)))
        assert int(dr.n_rounds) >= 2  # iterative, unlike one-shot

    def test_max_separated_centers_spread(self):
        c = max_separated_centers(jax.random.key(0), 8, 5)
        assert c.shape == (8, 5)
        assert bool(jnp.all((c >= 0) & (c <= 1)))
        # pairwise distances all nonzero
        d2 = jnp.sum((c[:, None] - c[None]) ** 2, -1) + jnp.eye(8)
        assert float(d2.min()) > 1e-3

    def test_pilot_subset_ignores_padding(self, setup):
        x, y, split = setup
        centers = pilot_subset_centers(jax.random.key(0), split, 3)
        # all centers within data range (padding rows are zero but excluded)
        assert bool(jnp.all(jnp.isfinite(centers)))

    def test_fed_kmeans_centers_shape(self, setup):
        x, y, split = setup
        centers = fed_kmeans_centers(jax.random.key(0), split, 3)
        assert centers.shape == (3, 4)

    def test_comm_rounds_grow_with_iterations(self, setup):
        x, y, split = setup
        dr = dem(jax.random.key(0), split, 3, init=1)
        assert dr.comm.rounds == int(dr.n_rounds)
        assert dr.comm.uplink_floats > dr.comm.rounds  # per-round stats


class TestMeshFacade:
    """``repro.api.DEM(k, mesh=...)``: what it refuses before it runs."""

    def test_clients_must_divide_over_the_shards(self, setup):
        from types import SimpleNamespace
        from repro.api import DEM
        _, _, split = setup
        four = SimpleNamespace(shape={"data": 4})
        with pytest.raises(ValueError, match="6 clients .* 4 shards"):
            DEM(3, mesh=four).run(split, key=jax.random.key(0))

    def test_mesh_takes_no_async_policy_or_sources(self, setup):
        from repro.api import DEM
        from repro.data.sources import ArraySource
        from repro.fed import AsyncPolicy
        _, _, split = setup
        mesh = jax.make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="async_policy"):
            DEM(3, mesh=mesh, async_policy=AsyncPolicy())
        sources = [ArraySource(np.asarray(split.data[c, :n]))
                   for c, n in enumerate(split.sizes)]
        with pytest.raises(TypeError, match="ClientSplit"):
            DEM(3, mesh=mesh).run(sources, key=jax.random.key(0))

    def test_one_shard_mesh_is_the_split_run(self, setup):
        """On a mesh of one device the sharded path sums the same clients
        in the same order as the split path."""
        from repro.api import DEM
        _, _, split = setup
        key = jax.random.key(2)
        one = DEM(3).run(split, key=key)
        mesh = DEM(3, mesh=jax.make_mesh((1,), ("data",))).run(split, key=key)
        assert int(one.n_rounds) == int(mesh.n_rounds)
        for f in ("weights", "means", "covs"):
            np.testing.assert_allclose(
                np.asarray(getattr(one.global_gmm, f)),
                np.asarray(getattr(mesh.global_gmm, f)), rtol=1e-5,
                atol=1e-6)
