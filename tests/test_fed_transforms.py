"""The uplink-transform seam (DESIGN.md §11): bit-identity anchors,
mask cancellation through the real backend reduces, DP mechanics and the
epsilon accountant, quantization, composition, and validation.

The bit-identity classes are the §11 contract's teeth: a run under
``Identity`` — and under ``PairwiseMask``, whose modular channel must
cancel exactly — is compared to a no-transform run with
``assert_array_equal``, never ``allclose``, on the split AND source
backends (the sharded backend is pinned in a forced-8-device subprocess,
mirroring tests/test_distributed.py).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

from repro.api import (DEM, DPConfig, FedEM, FedGenGMM, FedKMeans,
                       FitConfig, fit_federated)
from repro.core.em import SufficientStats
from repro.core.gmm import GMM
from repro.core.partition import partition
from repro.core.privacy import privatize_clients, privatize_gmm
from repro.data.sources import ArraySource
from repro.fed import (Compose, GaussianDP, Identity, PairwiseMask,
                       PayloadTransform, StochasticQuantize)
from repro.fed.runtime import _validate_transform
from repro.fed.transforms import (VAR_MAX, VAR_MIN, WEIGHT_FLOOR,
                                  clip_variances, gaussian_sigma,
                                  project_simplex)

# end-to-end fits: multi-second EM training loops on CPU
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def split():
    # features in [0,1]^d — the normalization the DP sensitivities assume
    rng = np.random.default_rng(7)
    x = rng.uniform(0.05, 0.95, size=(600, 3)).astype(np.float32)
    y = rng.integers(0, 2, size=600)
    return partition(rng, x, y, 4, "dirichlet", 100.0)


@pytest.fixture(scope="module")
def sources(split):
    parts = [np.asarray(split.data[i])[np.asarray(split.mask[i]) > 0.0]
             for i in range(split.data.shape[0])]
    assert all(len(p) for p in parts)
    return [ArraySource(p) for p in parts]


def assert_same_gmm(g1, g2):
    for f in ("weights", "means", "covs"):
        np.testing.assert_array_equal(np.asarray(getattr(g1, f)),
                                      np.asarray(getattr(g2, f)))


def _gmm(k=2, d=3, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.dirichlet(np.ones(k)).astype(np.float32)
    mu = rng.uniform(0.1, 0.9, (k, d)).astype(np.float32)
    var = rng.uniform(0.01, 0.2, (k, d)).astype(np.float32)
    return GMM(jnp.asarray(w), jnp.asarray(mu), jnp.asarray(var))


def _stats(k=2, d=3, seed=0):
    rng = np.random.default_rng(seed)
    return SufficientStats(
        s0=jnp.asarray(rng.uniform(1, 50, (k,)).astype(np.float32)),
        s1=jnp.asarray(rng.uniform(0, 30, (k, d)).astype(np.float32)),
        s2=jnp.asarray(rng.uniform(0, 20, (k, d)).astype(np.float32)),
        loglik=jnp.float32(-123.5), wsum=jnp.float32(100.0))


# ----------------------------------------------------------------------
# Bit-identity anchors: Identity and PairwiseMask leave fits untouched
# ----------------------------------------------------------------------

class TestBitIdentity:
    @pytest.mark.parametrize("transform", [Identity(), PairwiseMask()],
                             ids=["identity", "mask"])
    def test_dem_split_backend(self, split, transform):
        base = DEM(2, max_iter=4).run(split, key=jax.random.key(0))
        got = DEM(2, max_iter=4, transform=transform).run(
            split, key=jax.random.key(0))
        assert_same_gmm(base.global_gmm, got.global_gmm)
        assert int(base.n_rounds) == int(got.n_rounds)

    @pytest.mark.parametrize("transform", [Identity(), PairwiseMask()],
                             ids=["identity", "mask"])
    def test_dem_source_backend(self, sources, transform):
        base = DEM(2, max_iter=4).run(sources, key=jax.random.key(0))
        got = DEM(2, max_iter=4, transform=transform).run(
            sources, key=jax.random.key(0))
        assert_same_gmm(base.global_gmm, got.global_gmm)

    @pytest.mark.parametrize("transform", [Identity(), PairwiseMask()],
                             ids=["identity", "mask"])
    def test_fedem_split_backend(self, split, transform):
        kw = dict(participation=0.5, local_epochs=2, cohort="cyclic")
        base = FedEM(2, max_iter=6, **kw).run(split, key=jax.random.key(1))
        got = FedEM(2, max_iter=6, transform=transform, **kw).run(
            split, key=jax.random.key(1))
        assert_same_gmm(base.global_gmm, got.global_gmm)

    def test_fedkmeans_identity(self, split):
        base = FedKMeans(2, max_iter=4).run(split, key=jax.random.key(2))
        got = FedKMeans(2, max_iter=4, transform=Identity()).run(
            split, key=jax.random.key(2))
        np.testing.assert_array_equal(np.asarray(base.centers),
                                      np.asarray(got.centers))

    def test_fedgen_identity(self, split):
        base = FedGenGMM(k_clients=2, k_global=2).run(
            split, key=jax.random.key(3))
        got = FedGenGMM(k_clients=2, k_global=2, transform=Identity()).run(
            split, key=jax.random.key(3))
        assert_same_gmm(base.global_gmm, got.global_gmm)

    def test_sharded_backend_subprocess(self):
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import json
            import jax, jax.numpy as jnp
            import numpy as np
            from repro.core.partition import partition
            from repro.distributed import dem_sharded
            from repro.core.dem import fed_kmeans_centers
            from repro.fed import GaussianDP, Identity, PairwiseMask

            mesh = jax.make_mesh((8,), ("data",))
            rng = np.random.default_rng(0)
            x = rng.uniform(0.05, 0.95, (1600, 3)).astype(np.float32)
            y = rng.integers(0, 2, 1600)
            split = partition(rng, x, y, 16, "dirichlet", 100.0)
            data = jnp.asarray(split.data); mask = jnp.asarray(split.mask)
            centers = fed_kmeans_centers(jax.random.key(1), split, 2)

            def run(t):
                g, r = dem_sharded(mesh, jax.random.key(2), data, mask, 2,
                                   centers, max_rounds=4, transform=t)
                return [np.asarray(g.weights).tolist(),
                        np.asarray(g.means).tolist(),
                        np.asarray(g.covs).tolist()]

            base = run(None)
            out = {
                "identity_same": run(Identity()) == base,
                "mask_same": run(PairwiseMask()) == base,
                "dp_differs": run(GaussianDP(epsilon=2.0, rounds=4))
                              != base,
            }
            print(json.dumps(out))
        """)
        # the child must stay off the accelerator: the parent process
        # already holds it
        res = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, check=True,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        out = json.loads(res.stdout.strip().splitlines()[-1])
        assert out["identity_same"], "sharded Identity run drifted"
        assert out["mask_same"], "sharded PairwiseMask run drifted"
        assert out["dp_differs"], "sharded GaussianDP run did not perturb"


# ----------------------------------------------------------------------
# Mask cancellation: exactly zero through modular integer summation
# ----------------------------------------------------------------------

class TestMaskCancellation:
    def test_masks_sum_to_exact_zero(self):
        t = PairwiseMask(seed=3)
        key = jax.random.key(3)
        members = jnp.arange(5)
        payload = {"a": jnp.ones((4, 2), jnp.float32),
                   "b": jnp.zeros((3,), jnp.float32)}
        total = None
        for i in range(5):
            # every client derives from the SAME round key — that is
            # what lets pair (i, j) agree on the stream to cancel
            m = t.mask(key, payload, i, members)
            total = m if total is None else jax.tree.map(
                jnp.add, total, m)
        for leaf in jax.tree.leaves(total):
            np.testing.assert_array_equal(np.asarray(leaf), 0)

    def test_masked_channel_sum_equals_unmasked_lattice_sum(self):
        t = PairwiseMask(seed=9)
        key = jax.random.key(9)
        members = jnp.arange(4)
        rng = np.random.default_rng(1)
        payloads = [jnp.asarray(rng.normal(0, 1, (3, 2)).astype(np.float32))
                    for _ in range(4)]
        wires = [t.apply(key, (), p, i, members)
                 for i, p in enumerate(payloads)]
        masked_sum = sum(w["secagg"] for w in wires)
        plain_sum = sum(t._lattice(p) for p in payloads)
        np.testing.assert_array_equal(np.asarray(masked_sum),
                                      np.asarray(plain_sum))

    def test_single_wire_is_not_the_plain_lattice(self):
        # the whole point: one client's wire is masked (differs from its
        # own lattice) even though the SUM is exact
        t = PairwiseMask(seed=9)
        members = jnp.arange(4)
        p = jnp.ones((3, 2), jnp.float32)
        w = t.apply(jax.random.key(9), (), p, 0, members)
        assert np.any(np.asarray(w["secagg"]) != np.asarray(t._lattice(p)))

    def test_finish_strips_the_channel(self):
        t = PairwiseMask()
        total = {"payload": jnp.arange(3.0), "secagg": jnp.zeros(3,
                                                                 jnp.int32)}
        np.testing.assert_array_equal(np.asarray(t.finish(total)),
                                      np.asarray(jnp.arange(3.0)))


# ----------------------------------------------------------------------
# GaussianDP mechanics and the epsilon accountant
# ----------------------------------------------------------------------

class TestGaussianDP:
    def test_gmm_release_respects_projections(self):
        t = GaussianDP(epsilon=0.5)
        rel, n = t.apply(jax.random.key(0), t.traced(), (_gmm(), 200.0),
                         0, None)
        w = np.asarray(rel.weights)
        assert np.isclose(w.sum(), 1.0, atol=1e-6)
        assert (w > 0).all()
        mu = np.asarray(rel.means)
        assert (mu >= 0.0).all() and (mu <= 1.0).all()
        var = np.asarray(rel.covs)
        assert (var >= VAR_MIN).all() and (var <= VAR_MAX).all()
        assert float(n) == 200.0

    def test_noise_shrinks_with_epsilon(self):
        g = _gmm()
        key = jax.random.key(1)

        def err(eps):
            t = GaussianDP(epsilon=eps)
            rel, _ = t.apply(key, t.traced(), (g, 500.0), 0, None)
            return float(jnp.mean(jnp.abs(rel.means - g.means)))

        assert err(100.0) < err(0.2)

    def test_stats_release_floors_and_telemetry(self):
        t = GaussianDP(epsilon=1.0)
        s = _stats()
        rel = t.apply(jax.random.key(2), t.traced(), s, 0, None)
        assert (np.asarray(rel.s0) >= 0.0).all()
        assert (np.asarray(rel.s2) >= 0.0).all()
        assert np.any(np.asarray(rel.s1) != np.asarray(s.s1))
        # loglik / wsum are convergence telemetry, not model payload
        np.testing.assert_array_equal(np.asarray(rel.loglik),
                                      np.asarray(s.loglik))
        np.testing.assert_array_equal(np.asarray(rel.wsum),
                                      np.asarray(s.wsum))

    def test_unknown_payload_raises(self):
        t = GaussianDP()
        with pytest.raises(TypeError, match="SufficientStats"):
            t.apply(jax.random.key(0), t.traced(), jnp.zeros(3), 0, None)

    def test_accountant_depletes_across_rounds(self, split):
        # iterative run: each round spends epsilon/rounds; the ledger
        # reports spend at the REALIZED round count
        t = GaussianDP(epsilon=4.0, rounds=4)
        res = DEM(2, max_iter=4, tol=0.0, transform=t).run(
            split, key=jax.random.key(0))
        assert int(res.n_rounds) == 4
        assert np.isclose(res.comm.epsilon_spent, 4.0)
        assert np.isclose(res.comm.epsilon_spent,
                          t.epsilon_per_round() * int(res.n_rounds))

    def test_one_shot_spends_whole_budget_once(self, split):
        res = FedGenGMM(k_clients=2, k_global=2,
                        dp=DPConfig(epsilon=4.0)).run(
            split, key=jax.random.key(0))
        assert int(res.comm.rounds) == 1
        assert np.isclose(res.comm.epsilon_spent, 4.0)

    def test_dp_perturbs_but_preserves_structure(self, split):
        base = DEM(2, max_iter=4).run(split, key=jax.random.key(0))
        noisy = DEM(2, max_iter=4,
                    transform=GaussianDP(epsilon=2.0, rounds=4)).run(
            split, key=jax.random.key(0))
        assert np.any(np.asarray(noisy.global_gmm.means) !=
                      np.asarray(base.global_gmm.means))
        assert (np.asarray(noisy.global_gmm.covs) > 0).all()
        w = np.asarray(noisy.global_gmm.weights)
        assert np.isclose(w.sum(), 1.0, atol=1e-5)


# ----------------------------------------------------------------------
# Stochastic quantization
# ----------------------------------------------------------------------

class TestStochasticQuantize:
    def test_seeded_determinism_and_unbiased_grid(self):
        t = StochasticQuantize(bits=8)
        x = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, (64, 8)).astype(np.float32))
        a = t.apply(jax.random.key(5), (), x, 0, None)
        b = t.apply(jax.random.key(5), (), x, 0, None)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = t.apply(jax.random.key(6), (), x, 0, None)
        assert np.any(np.asarray(a) != np.asarray(c))
        # grid step bounds the per-element error
        step = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(a - x))) <= step + 1e-6

    def test_zero_and_int_leaves_pass_through(self):
        t = StochasticQuantize(bits=8)
        payload = {"z": jnp.zeros((4,), jnp.float32),
                   "i": jnp.arange(3, dtype=jnp.int32)}
        out = t.apply(jax.random.key(0), (), payload, 0, None)
        np.testing.assert_array_equal(np.asarray(out["z"]), 0.0)
        np.testing.assert_array_equal(np.asarray(out["i"]),
                                      np.asarray(payload["i"]))

    def test_ledger_reports_honest_wire_bytes(self, split):
        base = DEM(2, max_iter=4).run(split, key=jax.random.key(0))
        q8 = DEM(2, max_iter=4, transform=StochasticQuantize(bits=8)).run(
            split, key=jax.random.key(0))
        q16 = DEM(2, max_iter=4,
                  transform=StochasticQuantize(bits=16)).run(
            split, key=jax.random.key(0))
        assert q8.comm.uplink_itemsize == 1
        assert q16.comm.uplink_itemsize == 2
        # downlink (broadcast) stays f32 — the asymmetric-wire case
        assert q8.comm.downlink_bytes == q8.comm.downlink_floats * 4
        if int(q8.comm.rounds) == int(base.comm.rounds):
            assert q8.comm.uplink_bytes * 4 == base.comm.uplink_bytes

    def test_bits_is_structural_seed_is_not(self):
        assert StochasticQuantize(bits=8) != StochasticQuantize(bits=16)
        assert StochasticQuantize(seed=0) == StochasticQuantize(seed=9)
        assert hash(StochasticQuantize(seed=0)) == \
            hash(StochasticQuantize(seed=9))

    def test_validates_bits(self):
        with pytest.raises(ValueError, match="bits"):
            StochasticQuantize(bits=12)


# ----------------------------------------------------------------------
# Composition
# ----------------------------------------------------------------------

class TestCompose:
    def test_accounting_folds_through_stages(self):
        c = Compose((GaussianDP(epsilon=2.0, rounds=2),
                     StochasticQuantize(bits=8), PairwiseMask()))
        assert np.isclose(c.epsilon_per_round(), 1.0)
        assert c.wire_itemsize(4) == 4   # mask's int32 lattice wins
        assert c.additive_only
        c2 = Compose((GaussianDP(), StochasticQuantize(bits=16)))
        assert c2.wire_itemsize(4) == 2
        assert not c2.additive_only

    def test_member_reseed_does_not_change_equality(self):
        a = Compose((GaussianDP(seed=1), StochasticQuantize(bits=8)))
        b = Compose((GaussianDP(seed=2), StochasticQuantize(bits=8)))
        assert a == b and hash(a) == hash(b)
        assert a.seed != b.seed  # ...but the pipeline key differs

    def test_identity_mask_pipeline_is_bit_identical(self, split):
        base = DEM(2, max_iter=4).run(split, key=jax.random.key(0))
        got = DEM(2, max_iter=4,
                  transform=Compose((Identity(), PairwiseMask()))).run(
            split, key=jax.random.key(0))
        assert_same_gmm(base.global_gmm, got.global_gmm)

    def test_dp_then_quantize_runs(self, split):
        t = Compose((GaussianDP(epsilon=8.0, rounds=4),
                     StochasticQuantize(bits=16)))
        res = DEM(2, max_iter=4, transform=t).run(split,
                                                  key=jax.random.key(0))
        assert res.comm.uplink_itemsize == 2
        assert res.comm.epsilon_spent > 0.0

    def test_rejects_non_transform_members(self):
        with pytest.raises(TypeError, match="Compose members"):
            Compose((GaussianDP(), 42))


# ----------------------------------------------------------------------
# Property tests (offline hypothesis shim)
# ----------------------------------------------------------------------

class TestProperties:
    @settings(max_examples=25, deadline=None)
    @given(w=hst.lists(hst.floats(min_value=-2.0, max_value=2.0,
                                  allow_nan=False),
                       min_size=2, max_size=8))
    def test_project_simplex(self, w):
        out = np.asarray(project_simplex(jnp.asarray(w, jnp.float32)))
        assert np.isclose(out.sum(), 1.0, atol=1e-5)
        assert (out > 0.0).all()

    @settings(max_examples=25, deadline=None)
    @given(v=hst.lists(hst.floats(min_value=-10.0, max_value=10.0,
                                  allow_nan=False),
                       min_size=1, max_size=8))
    def test_clip_variances(self, v):
        out = np.asarray(clip_variances(jnp.asarray(v, jnp.float32)))
        assert (out >= VAR_MIN).all() and (out <= VAR_MAX).all()

    @settings(max_examples=10, deadline=None)
    @given(seed=hst.integers(min_value=0, max_value=2**31 - 1),
           eps=hst.floats(min_value=0.1, max_value=50.0))
    def test_seeded_release_is_deterministic(self, seed, eps):
        t = GaussianDP(epsilon=eps)
        key = jax.random.key(seed)
        a, _ = t.apply(key, t.traced(), (_gmm(), 100.0), 0, None)
        b, _ = t.apply(key, t.traced(), (_gmm(), 100.0), 0, None)
        assert_same_gmm(a, b)

    def test_sigma_matches_host_closed_form(self):
        import math
        got = float(gaussian_sigma(2.0, 0.5, 1e-5))
        want = math.sqrt(2.0 * math.log(1.25 / 1e-5)) * 2.0 / 0.5
        assert np.isclose(got, want, rtol=1e-6)


# ----------------------------------------------------------------------
# Validation and rejection
# ----------------------------------------------------------------------

class TestValidation:
    @pytest.mark.parametrize("kw,msg", [
        (dict(epsilon=0.0), "epsilon"),
        (dict(epsilon=-1.0), "epsilon"),
        (dict(delta=0.0), "delta"),
        (dict(delta=1.0), "delta"),
        (dict(min_count=0.0), "min_count"),
    ])
    def test_dpconfig_validates_at_construction(self, kw, msg):
        with pytest.raises(ValueError, match=msg):
            DPConfig(**kw)

    @pytest.mark.parametrize("kw,msg", [
        (dict(epsilon=0.0), "epsilon"),
        (dict(delta=2.0), "delta"),
        (dict(rounds=0), "rounds"),
        (dict(min_count=-1.0), "min_count"),
    ])
    def test_gaussian_dp_validates_at_construction(self, kw, msg):
        with pytest.raises(ValueError, match=msg):
            GaussianDP(**kw)

    def test_numeric_knobs_are_not_structural(self):
        # the zero-retrace contract's static half: eps/delta/rounds/seed
        # sweeps keep the transform equal and hash-equal
        assert GaussianDP(epsilon=1.0) == GaussianDP(epsilon=9.0, seed=3,
                                                     rounds=7)
        assert hash(GaussianDP(epsilon=1.0)) == \
            hash(GaussianDP(epsilon=9.0, seed=3, rounds=7))

    def test_full_covariance_release_raises_named_error(self):
        g = GMM(jnp.full((2,), 0.5),
                jnp.zeros((2, 3)), jnp.tile(jnp.eye(3), (2, 1, 1)))
        with pytest.raises(ValueError, match="full"):
            privatize_gmm(jax.random.key(0), g, 100.0, DPConfig())

    def test_privatize_clients_matches_transform(self):
        # the legacy entry point IS the transform: same key, same release
        g = _gmm()
        dp = DPConfig(epsilon=2.0)
        [rel] = privatize_clients(jax.random.key(4), [g], [150.0], dp)
        t = GaussianDP(epsilon=2.0, rounds=1)
        want, _ = t.apply(jax.random.fold_in(jax.random.key(4), 0),
                          t.traced(), (g, 150.0), 0, None)
        assert_same_gmm(rel, want)

    def test_run_rounds_rejects_non_transform(self, split):
        with pytest.raises(TypeError, match="PayloadTransform"):
            DEM(2, max_iter=2, transform=object()).run(
                split, key=jax.random.key(0))
        _validate_transform(Identity())  # and the real thing passes

    def test_one_shot_rejects_additive_only(self, split):
        with pytest.raises(ValueError, match="additive"):
            FedGenGMM(k_clients=2, k_global=2,
                      transform=PairwiseMask()).run(
                split, key=jax.random.key(0))
        with pytest.raises(ValueError, match="additive"):
            fit_federated(split, strategy="fedgen", k_clients=2,
                          k_global=2,
                          transform=Compose((PairwiseMask(),)),
                          key=jax.random.key(0))

    def test_dp_and_transform_are_exclusive(self):
        with pytest.raises(ValueError, match="not both"):
            FedGenGMM(k_clients=2, k_global=2, dp=DPConfig(),
                      transform=Identity())
        with pytest.raises(TypeError, match="DPConfig"):
            FedGenGMM(k_clients=2, k_global=2, dp=1.0)

    def test_builtins_satisfy_the_protocol(self):
        for t in (Identity(), GaussianDP(), StochasticQuantize(),
                  PairwiseMask(), Compose((Identity(),))):
            assert isinstance(t, PayloadTransform)
            assert dataclasses.is_dataclass(t)
            hash(t)  # static-arg requirement


# ----------------------------------------------------------------------
# The api seam end to end
# ----------------------------------------------------------------------

class TestApiSeam:
    def test_fit_federated_named_with_transform(self, split):
        base = fit_federated(split, strategy="dem", k=2,
                             config=FitConfig(max_iter=4),
                             key=jax.random.key(0))
        got = fit_federated(split, strategy="dem", k=2,
                            config=FitConfig(max_iter=4),
                            transform=Identity(), key=jax.random.key(0))
        assert_same_gmm(base.global_gmm, got.global_gmm)

    def test_fit_federated_custom_with_transform(self, split):
        from repro.core.dem import DEMStrategy
        strat = DEMStrategy(k=2, tol=1e-3)
        base = fit_federated(split, strategy=strat, max_rounds=4,
                             key=jax.random.key(0))
        got = fit_federated(split, strategy=strat, max_rounds=4,
                            transform=PairwiseMask(),
                            key=jax.random.key(0))
        assert_same_gmm(base.global_gmm, got.global_gmm)

    def test_same_seed_same_noise_across_backends(self, split, sources):
        # the per-client key derivation is backend-independent, so the
        # SAME DP draws land on split and source runs (float reduction
        # order may differ; the model must agree to f32 tolerance)
        t = GaussianDP(epsilon=3.0, rounds=4, seed=42)
        rs = DEM(2, max_iter=4, transform=t).run(split,
                                                 key=jax.random.key(0))
        ro = DEM(2, max_iter=4, transform=t).run(sources,
                                                 key=jax.random.key(0))
        np.testing.assert_allclose(np.asarray(rs.global_gmm.means),
                                   np.asarray(ro.global_gmm.means),
                                   atol=1e-4)

    def test_reseed_changes_noise(self, split):
        a = DEM(2, max_iter=4,
                transform=GaussianDP(epsilon=2.0, seed=0)).run(
            split, key=jax.random.key(0))
        b = DEM(2, max_iter=4,
                transform=GaussianDP(epsilon=2.0, seed=1)).run(
            split, key=jax.random.key(0))
        assert np.any(np.asarray(a.global_gmm.means) !=
                      np.asarray(b.global_gmm.means))
