"""Sharded federated runtime tests. These need >1 device, so they run in a
subprocess with a forced 8-device host platform (the main test process must
keep the single real device)."""
import json
import subprocess
import sys
import textwrap

import pytest

# end-to-end fits: multi-second EM training loops on CPU
pytestmark = pytest.mark.slow

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import fit_gmm, partition, fedgengmm
    from repro.core.dem import fed_kmeans_centers
    from repro.distributed import (dem_sharded, fed_kmeans_sharded,
                                   fedem_sharded, fedgen_sharded)

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    mus = np.array([[0,0,0],[5,5,5],[-5,5,-5]], np.float32)
    y = rng.integers(0, 3, 4000)
    x = (mus[y] + rng.normal(0, .5, (4000,3))).astype(np.float32)
    split = partition(rng, x, y, 16, "dirichlet", 0.5)
    data = jnp.asarray(split.data); mask = jnp.asarray(split.mask)
    xj = jnp.asarray(x)

    out = {}
    res = fedgen_sharded(mesh, jax.random.key(0), data, mask, k=3,
                         k_global=3, h=60)
    out["fed_ll"] = float(res.global_gmm.score(xj))

    centers = fed_kmeans_centers(jax.random.key(1), split, 3)
    gmm, rounds = dem_sharded(mesh, jax.random.key(2), data, mask, 3,
                              centers)
    out["dem_ll"] = float(gmm.score(xj))
    out["dem_rounds"] = int(rounds)

    bench = fit_gmm(jax.random.key(3), xj, 3)
    out["central_ll"] = float(bench.gmm.score(xj))

    # single-process (unsharded) reference for parity
    fr = fedgengmm(jax.random.key(0), split, k_clients=3, k_global=3, h=60)
    out["fed_ll_ref"] = float(fr.global_gmm.score(xj))

    # the iterative baselines on the SAME driver, mesh as client backend
    fe = fedem_sharded(mesh, jax.random.key(4), data, mask, 3,
                       participation=0.5, local_epochs=2)
    out["fedem_ll"] = float(fe.global_gmm.score(xj))
    out["fedem_rounds"] = int(fe.n_rounds)
    out["fedem_uplink"] = int(fe.comm.uplink_floats)
    out["fedem_itemsize"] = int(fe.comm.itemsize)

    km = fed_kmeans_sharded(mesh, jax.random.key(5), data, mask, 3)
    out["km_rounds"] = int(km.n_rounds)
    out["km_uplink"] = int(km.comm.uplink_floats)
    c = np.asarray(km.centers)
    out["km_center_err"] = float(max(
        min(np.linalg.norm(c - m, axis=1)) for m in mus))

    # -- DEM through the facade on a 4-chip mesh: 20 clients, 5 a shard --
    import importlib, sys
    sys.path.insert(0, "tests")
    from dem_reference import em_rounds, pooled_rows
    from repro.api import DEM, FitConfig
    from repro.core.dem import DEMStrategy
    from repro.core.em import init_from_means, init_from_means_sharded
    from repro.core.gmm import GMM
    from repro.core.kmeans import federated_kmeans, federated_kmeans_sharded
    from repro.fed.runtime import make_backend

    mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    d4, k4 = 6, 4
    rng = np.random.default_rng(4)
    mus4 = rng.uniform(0.2, 0.8, (k4, d4)).astype(np.float32)
    y4 = rng.integers(0, k4, 6000)
    x4 = (mus4[y4] + rng.normal(0, 0.08, (6000, d4))).astype(np.float32)
    split4 = partition(rng, x4, y4, 20, "dirichlet", 0.5)
    key4 = jax.random.key(3)
    gmm0 = DEMStrategy(k=k4).init_state(key4, make_backend(split4, mesh4)).gmm
    pooled = pooled_rows(split4)

    # how far a fit lies from ``other``, or else from the pooled
    # reference run from the same init model for the same rounds
    def deviation(res, other=None):
        g = res.global_gmm
        if other is None:
            w, mu, cov, ll = em_rounds(pooled, gmm0.weights, gmm0.means,
                                       gmm0.covs, int(res.n_rounds))
        else:
            h = other.global_gmm
            w, mu, cov, ll = (h.weights, h.means, h.covs,
                              other.log_likelihood)
        return {"weights": float(jnp.max(jnp.abs(g.weights - w))),
                "means": float(jnp.max(jnp.abs(g.means - mu))),
                "covs_rel": float(jnp.max(jnp.abs(g.covs - cov) / cov)),
                "ll": float(jnp.abs(res.log_likelihood - ll)),
                "rounds": int(res.n_rounds)}

    def fits(backend):
        cfg = FitConfig(backend=backend)
        return (DEM(k4, config=cfg).run(split4, key=key4),
                DEM(k4, config=cfg, mesh=mesh4).run(split4, key=key4))

    for backend in ("reference", "fused"):
        single, sharded = fits(backend)
        out[f"dem4_{backend}"] = {
            "mesh_vs_single": deviation(sharded, single),
            "rounds": [int(single.n_rounds), int(sharded.n_rounds)],
            "single_vs_ref": deviation(single),
            "mesh_vs_ref": deviation(sharded)}

    # planted faults, each in a program traced afresh
    real_psum = jax.lax.psum

    # shard 0's statistics left out of the sum
    def psum_drop(x, axis):
        first = jax.lax.axis_index(axis) == 0
        return real_psum(jax.tree.map(
            lambda v: jnp.where(first, jnp.zeros_like(v), v), x), axis)

    dem_mod = importlib.import_module("repro.core.dem")
    real_estep = dem_mod.e_step_stats

    def bf16(v):
        return v.astype(jnp.bfloat16).astype(jnp.float32)

    # the E-step from its operands rounded to bfloat16
    def estep_bf16(gmm, x, w, *a):
        return real_estep(GMM(bf16(gmm.weights), bf16(gmm.means),
                              bf16(gmm.covs)), bf16(x), w, *a)

    for name, owner, attr, fault in (("psum_drop", jax.lax, "psum",
                                      psum_drop),
                                     ("bf16", dem_mod, "e_step_stats",
                                      estep_bf16)):
        jax.clear_caches()
        real = getattr(owner, attr)
        setattr(owner, attr, fault)
        try:
            res = DEM(k4, mesh=mesh4).run(split4, key=key4)
        finally:
            setattr(owner, attr, real)
        out[f"fault_{name}"] = deviation(res)
    jax.clear_caches()

    # the sharded fed-kmeans init against the single-process one
    k_init = jax.random.split(key4)[0]
    c_one = federated_kmeans(k_init, jnp.asarray(split4.data), k4,
                             client_weights=jnp.asarray(split4.mask))
    c_mesh = federated_kmeans_sharded(
        k_init, jnp.asarray(split4.data), jnp.asarray(split4.mask),
        mesh=mesh4, k_global=k4)
    g_one = init_from_means(c_one, jnp.asarray(split4.data).reshape(-1, d4),
                            jnp.asarray(split4.mask).reshape(-1))
    g_mesh = init_from_means_sharded(c_mesh, jnp.asarray(split4.data),
                                     jnp.asarray(split4.mask), mesh=mesh4)
    out["init_centers"] = float(jnp.max(jnp.abs(c_one - c_mesh)))
    out["init_centers_dem"] = float(jnp.max(jnp.abs(c_one - gmm0.means)))
    out["init_covs_rel"] = float(jnp.max(jnp.abs(g_one.covs - g_mesh.covs)
                                         / g_one.covs))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_results():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_fedgen_close_to_centralized(sharded_results):
    r = sharded_results
    assert r["fed_ll"] > r["central_ll"] - 0.3, r


def test_sharded_dem_close_to_centralized(sharded_results):
    r = sharded_results
    assert r["dem_ll"] > r["central_ll"] - 0.3, r
    assert r["dem_rounds"] >= 2


def test_sharded_matches_single_process(sharded_results):
    """Mesh execution is a faithful implementation of the same algorithm."""
    r = sharded_results
    assert abs(r["fed_ll"] - r["fed_ll_ref"]) < 0.25, r


def test_sharded_fedem_fits_with_cohort_ledger(sharded_results):
    """FedEM under the mesh backend: partial participation still reaches
    a good fit, and the ledger is cohort-sized (8 of 16 clients per
    round, diag stats for k=3, d=3: 3 + 9 + 9 + 2 floats each)."""
    r = sharded_results
    assert r["fedem_ll"] > r["central_ll"] - 0.5, r
    # per-round cohort traffic + the one-shot fed-kmeans warm start the
    # whole population uplinks before round 0 (16 * (k*d + k) floats)
    assert r["fedem_uplink"] == \
        r["fedem_rounds"] * 8 * (3 + 9 + 9 + 2) + 16 * (9 + 3), r
    assert r["fedem_itemsize"] == 4


def test_sharded_fed_kmeans_recovers_centers(sharded_results):
    """FedKMeans under the mesh backend: per-center label stats psum'd
    per round (16 clients x (k + k*d + 1) floats), planted centers
    recovered. The post-rounds inertia rescore ships one extra scalar
    per client, once."""
    r = sharded_results
    assert r["km_center_err"] < 0.5, r
    # per-round label stats + the rescore scalar per client + the
    # fed-kmeans warm-start parameter uplink (16 * (k*d + k))
    assert r["km_uplink"] == \
        r["km_rounds"] * 16 * (3 + 9 + 1) + 16 + 16 * (9 + 3), r


# -- DEM through ``repro.api.DEM(k, mesh=...)``: 20 clients on 4 shards ----
#
# Both sides and the pooled float32 reference (tests/dem_reference.py) run
# the same EM rounds from the same init model; they differ only in the
# order of float32 sums (20 client sums and 4 shard sums against one sum
# over the pooled rows): about C u = 1.2e-6 relative per round for C = 20,
# u = 2^-24, which a few contracting EM rounds do not grow past 1e-5 on
# weights and means in [0, 1]. A variance is s2 / s0 - mean^2: the
# subtraction loses E[x^2] / var ~ 40 on these rows, hence its relative
# tolerance. The log-likelihood is one sum of per-row terms.
TOL = {"weights": 1e-5, "means": 1e-5, "covs_rel": 5e-4, "ll": 1e-4}


def _within(dev) -> bool:
    return all(dev[name] <= tol for name, tol in TOL.items())


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_mesh_dem_matches_single_process(sharded_results, backend):
    """``DEM(k, mesh=m).run(split, key)`` against ``DEM(k).run(split,
    key)``: the same rounds and the same model up to summation order."""
    r = sharded_results[f"dem4_{backend}"]
    assert r["rounds"][0] == r["rounds"][1], r
    assert _within(r["mesh_vs_single"]), r


@pytest.mark.parametrize("backend", ["reference", "fused"])
@pytest.mark.parametrize("side", ["single", "mesh"])
def test_dem_matches_pooled_reference(sharded_results, backend, side):
    r = sharded_results[f"dem4_{backend}"][f"{side}_vs_ref"]
    assert _within(r), r


@pytest.mark.parametrize("fault", ["psum_drop", "bf16"])
def test_planted_fault_is_caught(sharded_results, fault):
    """One shard's statistics left out of the round's psum, or an E-step
    from bfloat16 operands, lands outside the tolerances."""
    r = sharded_results[f"fault_{fault}"]
    assert not _within(r), r


@pytest.mark.parametrize("what", ["init_centers", "init_centers_dem"])
def test_sharded_fed_kmeans_init_matches(sharded_results, what):
    """The sharded fed-kmeans init draws the single-process key schedule:
    the same local fits and server clustering, gathered, give the same
    centers (standalone, and as DEM's init on a mesh draws them)."""
    assert sharded_results[what] <= 1e-6, sharded_results[what]


def test_sharded_init_moments_match(sharded_results):
    """The data variance from per-shard moments and one psum equals the
    resident two-pass form up to rounding."""
    assert sharded_results["init_covs_rel"] <= 1e-5, sharded_results
