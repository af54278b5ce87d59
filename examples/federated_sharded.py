"""The mesh-collective federated runtime: clients as data-axis shards.
FedGenGMM = ONE all-gather; DEM = one psum per round. Run with a forced
multi-device host platform:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/federated_sharded.py
"""
import os

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import DEM, FitConfig, GMMEstimator
from repro.core import partition
from repro.distributed import fedgen_sharded

mesh = jax.make_mesh((len(jax.devices()),), ("data",))
print(f"mesh: {mesh}")

rng = np.random.default_rng(0)
mus = rng.normal(0, 5, (4, 6)).astype(np.float32)
y = rng.integers(0, 4, 6000)
x = (mus[y] + rng.normal(0, 0.5, (6000, 6))).astype(np.float32)
split = partition(rng, x, y, 16, "dirichlet", 0.3)
data, mask = jnp.asarray(split.data), jnp.asarray(split.mask)
xj = jnp.asarray(x)

# the sharded runtime consumes the same FitConfig as the facades
cfg = FitConfig()
res = fedgen_sharded(mesh, jax.random.key(0), data, mask, k=4, k_global=4,
                     h=80, config=cfg)
print(f"FedGenGMM (1 all-gather):   ll={float(res.global_gmm.score(xj)):.4f}")

# DEM through the facade: the fed-kmeans init and every round on the shards
dem = DEM(4, mesh=mesh, config=cfg.replace(max_iter=100)).run(
    split, key=jax.random.key(2))
print(f"DEM ({int(dem.n_rounds)} psum rounds):       "
      f"ll={float(dem.global_gmm.score(xj)):.4f}")

bench = GMMEstimator(4, seed=3).fit(xj)
print(f"non-federated benchmark:    ll={float(bench.score(xj)):.4f}")
