"""Public-API snapshot: the ``repro.api`` surface and the ``FitConfig``
field table are frozen here so accidental drift fails the tier-1 lane.

Growing the surface is fine — do it deliberately by updating BOTH the
code and these snapshots (and DESIGN.md §8, which documents the same
table). Removing or renaming anything here is a breaking change to every
facade caller (examples, benchmarks, downstream scenarios) and must say
so in the PR.
"""
import dataclasses
import inspect

import repro.api as api
from repro.api import (DEM, FedEM, FedGenGMM, FedKMeans, FitConfig,
                       GMMEstimator, KMeansEstimator)

# The one public surface (DESIGN.md §8/§9). Sorted to make diffs readable.
EXPECTED_EXPORTS = sorted([
    "FitConfig",
    "DPConfig",
    "GMMEstimator",
    "KMeansEstimator",
    "FedGenGMM",
    "DEM",
    "FedEM",
    "FedKMeans",
    "fit_federated",
    "score",
    "log_prob",
    "bic",
    "Scorer",
    "DEFAULT_SOURCE_CHUNK",
])

# FitConfig field table: (name, default) in declaration order — the §8
# contract. A changed default silently changes every facade fit, so it is
# pinned as hard as the names. tol/max_iter default "auto" = per-algorithm
# resolution (EM 1e-3/200, k-means 1e-4/100 — TOL_DEFAULTS /
# MAX_ITER_DEFAULTS in repro.core.config).
EXPECTED_FITCONFIG_FIELDS = [
    ("backend", "auto"),
    ("chunk_size", "auto"),
    ("covariance_type", "diag"),
    ("reg_covar", 1e-6),
    ("tol", "auto"),
    ("max_iter", "auto"),
    ("init", "auto"),
    ("seed", 0),
]

# The federated runners' constructor keywords, in order: each is a knob
# every caller may pass, so adding one is deliberate and removing one is
# a breaking change. ``mesh`` shards DEM's clients over a device mesh.
EXPECTED_RUNNER_KEYWORDS = {
    "FedGenGMM": ["k_clients", "k_global", "k_candidates", "h", "synthetic",
                  "dp", "transform", "config", "overrides"],
    "DEM": ["k", "transform", "async_policy", "mesh", "config", "overrides"],
    "FedEM": ["k", "participation", "local_epochs", "cohort", "cohort_seed",
              "stragglers", "transform", "async_policy", "config",
              "overrides"],
    "FedKMeans": ["k", "transform", "config", "overrides"],
}

# Deprecation shims must never leak into the facade: they live in
# repro.core, warn on use, and forward — the public surface stays the
# estimator/runner set above.
SHIM_NAMES = [
    "fit_gmm_streaming",
    "fedgengmm_from_sources",
    "dem_from_sources",
    "train_locals_from_sources",
    "federated_kmeans_from_sources",
]


class TestSurface:
    def test_all_matches_snapshot(self):
        assert sorted(api.__all__) == EXPECTED_EXPORTS

    def test_exports_resolve(self):
        for name in api.__all__:
            assert getattr(api, name, None) is not None, name

    def test_no_extra_public_names(self):
        """Anything public-looking in the module must be declared in
        __all__ — the facade cannot grow a shadow surface."""
        public = {n for n in dir(api)
                  if not n.startswith("_")
                  and n not in ("estimators", "serving")}
        # submodule imports that back the package are not surface
        assert public - set(api.__all__) == set()


class TestFitConfigFields:
    def test_field_table(self):
        fields = [(f.name, f.default) for f in dataclasses.fields(FitConfig)]
        assert fields == EXPECTED_FITCONFIG_FIELDS

    def test_frozen_and_hashable(self):
        cfg = FitConfig()
        try:
            cfg.tol = 1.0
            raise AssertionError("FitConfig must be frozen")
        except dataclasses.FrozenInstanceError:
            pass
        assert hash(FitConfig(chunk_size=64)) == hash(FitConfig(chunk_size=64))
        assert FitConfig() == FitConfig()


class TestFacadeShape:
    """The estimator-style contract every future scenario PR plugs into."""

    def test_fit_signatures(self):
        for cls in (GMMEstimator, KMeansEstimator):
            params = inspect.signature(cls.fit).parameters
            assert "data" in params and "key" in params
            assert "sample_weight" in params

    def test_run_signatures(self):
        for cls in (FedGenGMM, DEM, FedEM, FedKMeans):
            params = inspect.signature(cls.run).parameters
            assert "clients" in params and "key" in params

    def test_constructors_take_config(self):
        for cls in (GMMEstimator, KMeansEstimator, FedGenGMM, DEM, FedEM,
                    FedKMeans):
            assert "config" in inspect.signature(cls.__init__).parameters

    def test_runner_keywords(self):
        for name, want in EXPECTED_RUNNER_KEYWORDS.items():
            params = inspect.signature(getattr(api, name).__init__).parameters
            assert list(params)[1:] == want, name

    def test_strategy_seam_signature(self):
        params = inspect.signature(api.fit_federated).parameters
        assert "clients" in params and "strategy" in params
        assert "config" in params and "key" in params


class TestNoShimLeak:
    """The `*_from_sources` / `fit_gmm_streaming` deprecation shims are
    internal: none may appear in the facade's exports or attributes, and
    none may appear as a FitConfig field (the snapshot above would catch
    a field, this catches the names)."""

    def test_shims_not_exported(self):
        for name in SHIM_NAMES:
            assert name not in api.__all__, name
            assert not hasattr(api, name), name

    def test_shims_not_fitconfig_fields(self):
        fields = {f.name for f in dataclasses.fields(FitConfig)}
        assert fields.isdisjoint(SHIM_NAMES)
