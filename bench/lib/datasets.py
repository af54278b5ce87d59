"""Client slabs, scoring pools and the served model, made on the device.

One jitted call per product, from the run's key; the row generator of the
configuration (``bench/data/<generator>.py``) supplies the rows. Features
are min-max scaled with the training rows' range, as
``repro.data.datasets`` does, and every seed gives the same shapes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from lib import partition


def run_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _minmax(x: jax.Array, valid: jax.Array):
    lo = jnp.min(jnp.where(valid[:, None], x, jnp.inf), axis=0)
    hi = jnp.max(jnp.where(valid[:, None], x, -jnp.inf), axis=0)
    return lo, jnp.maximum(hi - lo, 1e-12)


@partial(jax.jit, static_argnames=("gen", "n_max"))
def _slab(key, counts, class_map, tab, *, gen, n_max):
    c, m = counts.shape
    cum = jnp.cumsum(counts, axis=1)
    j = jnp.arange(n_max)
    col = jax.vmap(lambda cu: jnp.searchsorted(cu, j, side="right"))(cum)
    valid = col < m
    labels = class_map[jnp.minimum(col, m - 1)]
    x = gen.rows(key, labels.reshape(-1), tab)
    flat_valid = valid.reshape(-1)
    lo, span = _minmax(x, flat_valid)
    x = jnp.where(flat_valid[:, None], (x - lo) / span, 0.0)
    return x.reshape(c, n_max, -1), valid.astype(jnp.float32)


def client_slab(gen, cfg: dict, seed: int, key: jax.Array):
    """The configuration's training rows over its clients, scaled to
    their range -> (data (C, N_max, d), mask (C, N_max), sizes (C,),
    class counts (C, M))."""
    counts, class_map = partition.client_counts(
        cfg["partition"], cfg["train_rows"], cfg["classes"], cfg["clients"],
        seed)
    n_max = int(counts.sum(axis=1).max())
    data, mask = _slab(key, jnp.asarray(counts, jnp.int32),
                       jnp.asarray(class_map, jnp.int32), gen.tables(),
                       gen=gen, n_max=n_max)
    sizes = counts.sum(axis=1)
    class_counts = np.zeros_like(counts)
    class_counts[:, class_map] = counts
    return data, mask, sizes, class_counts


@partial(jax.jit, static_argnames=("gen", "n_train", "n_test", "n_attack",
                                   "classes"))
def _pool(key, tab, *, gen, n_train, n_test, n_attack, classes):
    k_tr, k_lab, k_te, k_att, k_perm = jax.random.split(key, 5)
    totals = partition.class_totals(n_train, classes)
    y_tr = jnp.repeat(jnp.arange(classes), jnp.asarray(totals),
                      total_repeat_length=n_train)
    x_tr = gen.rows(k_tr, y_tr, tab)
    lo, span = _minmax(x_tr, jnp.ones(n_train, bool))
    x_tr = (x_tr - lo) / span
    # the served model: each class's share, mean and variance
    onehot = jax.nn.one_hot(y_tr, classes, dtype=jnp.float32)
    cnt = onehot.sum(axis=0)
    hp = jax.lax.Precision.HIGHEST
    mean = jnp.matmul(onehot.T, x_tr, precision=hp) / cnt[:, None]
    var = jnp.matmul(onehot.T, x_tr * x_tr, precision=hp) / cnt[:, None] \
        - mean * mean
    weights = cnt / n_train
    y = jax.random.randint(k_lab, (n_test + n_attack,), 0, classes)
    x = gen.rows(k_te, y, tab)
    x_att = gen.attack(k_att, x[n_test:], y[n_test:], tab)
    x = (jnp.concatenate([x[:n_test], x_att]) - lo) / span
    order = jax.random.permutation(k_perm, n_test + n_attack)
    return x[order], (weights, mean, jnp.maximum(var, 0.0) + 1e-6)


def scoring_pool(gen, cfg: dict, key: jax.Array):
    """Held-out and attack rows, shuffled together, and the served
    model's (weights, means, variances) from per-class moments of the
    training rows -> device arrays."""
    n_test = int(cfg["test_rows"])
    share = float(cfg["anomaly_share"])
    n_attack = int(n_test * share / (1 - share)) + 1
    return _pool(key, gen.tables(), gen=gen, n_train=int(cfg["train_rows"]),
                 n_test=n_test, n_attack=n_attack,
                 classes=int(cfg["classes"]))
