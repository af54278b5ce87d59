"""Public wrappers around the Pallas kernels.

Handle padding to TPU tile boundaries (``LANES`` = 128, tunable N/K blocks)
and parameter re-packing into the matmul-identity form. :func:`prepare`
pads rows once into a :class:`Slab`, which ``estep_stats`` and
``kmeans_assign`` take in place of a raw ``(N, d)`` array: a raw array is
padded on every call, a slab goes to the kernel as it is, so a loop that
prepares its rows before it iterates pads them once (DESIGN.md §5). With
``interpret=None`` a kernel compiles for the TPU (Mosaic) when JAX's
default backend is a TPU, and runs in Pallas interpret mode anywhere else:
the kernel body evaluated by XLA on the host, slow, and there for parity
tests on the CPU. Interpret mode computes the same float32 math but does
not promise the TPU's bits. On the chip, ``chip_smoke.py`` checks that the
compiled programs hold the Mosaic kernel (``tpu_custom_call``), so a fall
into interpret mode or the reference path fails there.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels.estep_stats import estep_stats_pallas
from repro.kernels.gmm_logpdf import gmm_logpdf_pallas
from repro.kernels.kmeans_assign import kmeans_assign_pallas

LOG_2PI = 1.8378770664093453
_NEG_BIG = -1e30
#: the TPU's vector lane width: the kernels pad features and components
#: to a multiple of it
LANES = 128
#: rows per block of ``estep_stats`` and ``kmeans_assign``: one slab
#: serves both
BLOCK_N = 512


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def padded_lanes(d: int) -> int:
    """Width a kernel computes over for ``d`` features or components."""
    return _round_up(d, LANES)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["x", "w"], meta_fields=["n", "d"])
@dataclasses.dataclass(frozen=True)
class Slab:
    """Rows padded for the kernels: ``x`` ``(n_pad, d_pad)`` float32 with
    zeros past row ``n`` and feature ``d``, and ``w`` the ``(n_pad, 1)``
    weight column, 0 on padded rows. ``n`` and ``d`` are the true shape
    (static). A pytree, so it rides through ``jit`` and ``vmap`` (a
    vmapped slab has a leading client axis on ``x`` and ``w``)."""
    x: jax.Array
    w: jax.Array
    n: int
    d: int


def prepare(x: jax.Array, sample_weight: jax.Array | None = None, *,
            block_n: int = BLOCK_N) -> Slab:
    """Pad ``(N, d)`` rows to ``block_n`` rows and 128 lanes, with their
    weight column (1 where ``sample_weight`` is None): the operands a
    kernel reads, built once."""
    n, d = x.shape
    xp = _pad_rows(x, block_n)
    w = jnp.ones(n, jnp.float32) if sample_weight is None else sample_weight
    wp = jnp.zeros((xp.shape[0], 1), jnp.float32).at[:n, 0].set(w)
    return Slab(xp, wp, n, d)


def _pad_rows(x: jax.Array, block_n: int) -> jax.Array:
    """``(N, d)`` -> float32 ``(N`` rounded up to ``block_n``, ``d``
    rounded up to 128), zero-padded."""
    n, d = x.shape
    return jnp.zeros((_round_up(n, block_n), padded_lanes(d)),
                     jnp.float32).at[:n, :d].set(x)


def slab_bytes(n: int, d: int, weights: bool = True) -> int:
    """Device bytes of :func:`prepare`'s slab of ``(n, d)`` rows: the
    padded rows, and with ``weights`` the weight column, which the TPU
    lays out one 128-lane row per row."""
    lanes = padded_lanes(d) + (LANES if weights else 0)
    return _round_up(n, BLOCK_N) * lanes * 4


def _as_slab(x, sample_weight, block_n: int) -> Slab:
    """``x`` as a slab: prepared here, or as its loop prepared it."""
    if not isinstance(x, Slab):
        return prepare(x, sample_weight, block_n=block_n)
    if sample_weight is not None:
        raise ValueError("a prepared slab carries its weights; pass them "
                         "to prepare()")
    return x


def _pack_params(means, variances, log_weights, d_pad, k_pad, pad_c=0.0):
    """Repack (means, variances) into (a, b, c) for the matmul identity,
    padded to (d_pad, k_pad)."""
    k, d = means.shape
    inv_var = 1.0 / variances
    a = jnp.zeros((d_pad, k_pad), jnp.float32).at[:d, :k].set(
        (-0.5 * inv_var).T)
    b = jnp.zeros((d_pad, k_pad), jnp.float32).at[:d, :k].set(
        (means * inv_var).T)
    cvec = -0.5 * (jnp.sum(means * means * inv_var, axis=-1)
                   + jnp.sum(jnp.log(variances), axis=-1) + d * LOG_2PI)
    if log_weights is not None:
        cvec = cvec + log_weights
    c = jnp.full((1, k_pad), pad_c, jnp.float32).at[0, :k].set(cvec)
    return a, b, c


def gmm_logpdf(x: jax.Array, means: jax.Array, variances: jax.Array,
               log_weights: jax.Array | None = None, *,
               block_n: int = 256, block_k: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Diagonal-GMM per-component log density, (N, d) -> (N, K) float32."""
    interpret = _auto_interpret(interpret)
    n, d = x.shape
    k = means.shape[0]
    k_pad, d_pad = _round_up(k, block_k), padded_lanes(d)
    a, b, c = _pack_params(means, variances, log_weights, d_pad, k_pad)
    xp = _pad_rows(x, block_n)
    out = gmm_logpdf_pallas(xp, a, b, c, block_n=block_n, block_k=block_k,
                            interpret=interpret)
    return out[:n, :k]


def estep_stats(x: jax.Array | Slab, means: jax.Array,
                variances: jax.Array, log_weights: jax.Array,
                sample_weight: jax.Array | None = None, *,
                block_n: int = BLOCK_N, interpret: bool | None = None):
    """Fused E-step statistics of ``(N, d)`` rows, or of a :class:`Slab`
    (which carries its weights). Returns (s0 (K,), s1 (K,d), s2 (K,d),
    ll)."""
    interpret = _auto_interpret(interpret)
    slab = _as_slab(x, sample_weight, block_n)
    k, d = means.shape[0], slab.d
    d_pad, k_pad = slab.x.shape[-1], padded_lanes(k)
    a, b, c = _pack_params(means, variances, log_weights, d_pad, k_pad,
                           pad_c=_NEG_BIG)
    s0, s1, s2, ll = estep_stats_pallas(slab.x, slab.w, a, b, c,
                                        block_n=block_n, interpret=interpret)
    return s0[0, :k], s1[:k, :d], s2[:k, :d], ll[0, 0]


def kmeans_assign(x: jax.Array | Slab, centers: jax.Array, *,
                  block_n: int = BLOCK_N, interpret: bool | None = None):
    """Nearest-center assignment of ``(N, d)`` rows or of a :class:`Slab`.
    Returns ((N,) int32, (N,) squared dist)."""
    interpret = _auto_interpret(interpret)
    slab = _as_slab(x, None, block_n)
    k, n, d = centers.shape[0], slab.n, slab.d
    d_pad, k_pad = slab.x.shape[-1], padded_lanes(k)
    ct = jnp.zeros((d_pad, k_pad), jnp.float32).at[:d, :k].set(centers.T)
    c2 = jnp.full((1, k_pad), 1e30, jnp.float32).at[0, :k].set(
        jnp.sum(centers * centers, axis=1))
    idx, d2 = kmeans_assign_pallas(slab.x, ct, c2, block_n=block_n,
                                   interpret=interpret)
    return idx[:n, 0], d2[:n, 0]
