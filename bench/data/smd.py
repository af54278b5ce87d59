"""SMD-like server-machine metrics, made on the device from a key.

The construction of ``repro.data.datasets.smd_like``, ported to
``jax.random``: 38 metrics of 28 machines; each machine has a fixed level
and noise scale per metric, and a shared load factor, Beta(2, 5) per row,
drives the metrics through a fixed coupling. Malfunction rows pick 3 to 8
metrics at random and either spike them by U(0.5, 1.2) or flatline them
at a tenth of the machine's level. The fixed tables come from the same
fixed numpy seed (13) and draws as the original; the row values follow
the same distributions from another random stream, and the original's
Python loop over malfunction rows is one vectorised draw here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

D = 38
MACHINES = 28


def tables() -> dict:
    srng = np.random.default_rng(13)
    mu = srng.uniform(0.2, 0.8, (MACHINES, D))
    scale = srng.uniform(0.02, 0.12, (MACHINES, D))
    coupling = srng.uniform(0, 0.6, (1, D))
    return {"mu": jnp.asarray(mu, jnp.float32),
            "scale": jnp.asarray(scale, jnp.float32),
            "coupling": jnp.asarray(coupling, jnp.float32)}


def rows(key: jax.Array, labels: jax.Array, tab: dict) -> jax.Array:
    """(n,) machine labels -> (n, 38) unscaled float32 rows."""
    n = labels.shape[0]
    k1, k2 = jax.random.split(key)
    load = jax.random.beta(k1, 2.0, 5.0, (n, 1), jnp.float32)
    return (tab["mu"][labels] + load * tab["coupling"]
            + jax.random.normal(k2, (n, D), jnp.float32)
            * tab["scale"][labels])


def attack(key: jax.Array, x: jax.Array, labels: jax.Array,
           tab: dict) -> jax.Array:
    """Malfunctions: per row, 3-8 random metrics spike or flatline."""
    n = x.shape[0]
    kk, kc, km, ka = jax.random.split(key, 4)
    k = jax.random.randint(kk, (n, 1), 3, 9)
    rank = jnp.argsort(jnp.argsort(jax.random.uniform(kc, (n, D)), axis=1),
                       axis=1)
    chosen = rank < k
    spike = jax.random.uniform(km, (n, 1)) < 0.5
    amount = jax.random.uniform(ka, (n, 1), jnp.float32, 0.5, 1.2)
    flat = tab["mu"][labels] * 0.1
    out = jnp.where(spike, x + amount, flat)
    return jnp.where(chosen, out, x)
