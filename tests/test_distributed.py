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
