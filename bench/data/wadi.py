"""WADI-like sensor rows, made on the device from a key.

The construction of ``repro.data.datasets.wadi_like``, ported to
``jax.random`` so that a million rows cost one jitted call and no host
time: 84 sensor features; a slow common drift (8 latent factors with a
fixed loading) plus station noise; class m shifted by ``(m - 1) * beta *
0.1`` with noise 0.1 (the paper's artificial classes); attack rows push a
fixed group of 12 sensors by U(0.8, 1.6) in a fixed direction. The fixed
tables come from the same fixed numpy seed (11) and draws as the
original, so they are equal to it; the row values follow the same
distributions from another random stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

D = 84
BETA = 0.3


def tables() -> dict:
    wrng = np.random.default_rng(11)
    loading = wrng.normal(0, 1, (8, D)) * 0.2
    attacked = wrng.choice(D, 12, replace=False)
    sign = np.sign(wrng.normal(0, 1, (1, 12)))
    return {"loading": jnp.asarray(loading, jnp.float32),
            "attacked": jnp.asarray(attacked, jnp.int32),
            "sign": jnp.asarray(sign, jnp.float32)}


def rows(key: jax.Array, labels: jax.Array, tab: dict) -> jax.Array:
    """(n,) class labels -> (n, 84) unscaled float32 rows."""
    n = labels.shape[0]
    k1, k2, k3 = jax.random.split(key, 3)
    t = jax.random.normal(k1, (n, 8), jnp.float32)
    base = 0.5 + jnp.matmul(t, tab["loading"],
                            precision=jax.lax.Precision.HIGHEST) \
        + 0.05 * jax.random.normal(k2, (n, D), jnp.float32)
    shift = (labels.astype(jnp.float32) - 1.0) * BETA * 0.1
    return base + shift[:, None] + 0.1 * jax.random.normal(k3, (n, D),
                                                           jnp.float32)


def attack(key: jax.Array, x: jax.Array, labels: jax.Array,
           tab: dict) -> jax.Array:
    """Attack mode: a coordinated push on the fixed sensor group."""
    push = jax.random.uniform(key, (x.shape[0], 1), jnp.float32, 0.8, 1.6)
    return x.at[:, tab["attacked"]].add(push * tab["sign"])
