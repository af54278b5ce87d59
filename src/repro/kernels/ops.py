"""Public wrappers around the Pallas kernels.

Handle padding to TPU tile boundaries (``LANES`` = 128, tunable N/K blocks)
and parameter re-packing into the matmul-identity form. :func:`prepare`
pads rows once into a :class:`Slab`, which ``estep_stats`` and
``kmeans_assign`` take in place of a raw ``(N, d)`` array: a raw array is
padded on every call, a slab goes to the kernel as it is, so a loop that
prepares its rows before it iterates pads them once (DESIGN.md §5). The
E-step computes a slab's row blocks up to its last weighted row, and a
vmap over it, nested or not, is one kernel call over every client. With
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
from jax import custom_batching

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
                   data_fields=["x", "w", "blocks"],
                   meta_fields=["n", "d", "block_n"])
@dataclasses.dataclass(frozen=True)
class Slab:
    """Rows padded for the kernels: ``x`` ``(n_pad, d_pad)`` float32 with
    zeros past row ``n`` and feature ``d``, ``w`` the ``(n_pad, 1)``
    weight column, 0 on padded rows, and ``blocks`` the int32 count of
    ``block_n``-row blocks up to the last row of nonzero weight: the
    blocks the E-step computes (the rest would add exactly zero). ``n``,
    ``d`` and ``block_n`` are static. A pytree, so it rides through
    ``jit`` and ``vmap`` (a vmapped slab has a leading client axis on
    ``x``, ``w`` and ``blocks``)."""
    x: jax.Array
    w: jax.Array
    blocks: jax.Array
    n: int
    d: int
    block_n: int = BLOCK_N


def prepare(x: jax.Array, sample_weight: jax.Array | None = None, *,
            block_n: int = BLOCK_N) -> Slab:
    """Pad ``(N, d)`` rows to ``block_n`` rows and 128 lanes, with their
    weight column (1 where ``sample_weight`` is None) and the count of
    blocks that hold a weighted row: the operands a kernel reads, built
    once. The count reads the ``(N,)`` weights here, so no loop reads the
    padded column for it."""
    n, d = x.shape
    xp = _pad_rows(x, block_n)
    if sample_weight is None:
        w, rows = jnp.ones(n, jnp.float32), n
    else:
        w = sample_weight
        rows = jnp.max(jnp.where(w != 0, jnp.arange(1, n + 1), 0),
                       initial=0)
    wp = jnp.zeros((xp.shape[0], 1), jnp.float32).at[:n, 0].set(w)
    blocks = jnp.asarray((rows + block_n - 1) // block_n, jnp.int32)
    return Slab(xp, wp, blocks, n, d, block_n)


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


@functools.lru_cache(maxsize=None)
def _estep_clients(per_client: tuple, block_n: int, interpret: bool):
    """``estep_stats_pallas`` as one op over the clients its ``blocks``
    counts, with its own batching rule: a vmap over the op folds the
    mapped axis into the kernel's client axis, so a vmapped call, nested
    or inside a vmapped ``while_loop``, is still one kernel over every
    client. (JAX's own rule for a ``pallas_call`` would run a batched
    scalar-prefetch operand one client at a time, slicing each client's
    slab out.) An operand shared by every client stays shared."""

    @custom_batching.custom_vmap
    def op(blocks, *operands):
        return estep_stats_pallas(blocks, *operands, per_client=per_client,
                                  block_n=block_n, interpret=interpret)

    @op.def_vmap
    def _fold(axis_size, in_batched, blocks, *operands):
        clients = blocks.shape[-1]

        def fold(v, mapped, has_clients):
            if not mapped:
                v = jnp.broadcast_to(v, (axis_size, *v.shape))
            if not has_clients:
                if clients == 1:
                    return v
                v = jnp.broadcast_to(v[:, None],
                                     (axis_size, clients, *v.shape[1:]))
            return v.reshape(axis_size * clients, *v.shape[2:])

        folded, shared = [], []
        for v, mapped, has in zip(operands, in_batched[1:], per_client):
            shared.append(not (mapped or has))
            folded.append(v if shared[-1] else fold(v, mapped, has))
        outs = _estep_clients(tuple(not s for s in shared), block_n,
                              interpret)(fold(blocks, in_batched[0], True),
                                         *folded)
        return (tuple(o.reshape(axis_size, clients, *o.shape[1:])
                      for o in outs), (True,) * len(outs))

    return op


def estep_stats(x: jax.Array | Slab, means: jax.Array,
                variances: jax.Array, log_weights: jax.Array,
                sample_weight: jax.Array | None = None, *,
                block_n: int = BLOCK_N, interpret: bool | None = None):
    """Fused E-step statistics of ``(N, d)`` rows, or of a :class:`Slab`
    (which carries its weights and its row block; ``block_n`` applies to
    raw rows). Only the row blocks up to the last weighted row are
    computed. Returns (s0 (K,), s1 (K,d), s2 (K,d), ll)."""
    interpret = _auto_interpret(interpret)
    slab = _as_slab(x, sample_weight, block_n)
    k, d = means.shape[0], slab.d
    d_pad, k_pad = slab.x.shape[-1], padded_lanes(k)
    a, b, c = _pack_params(means, variances, log_weights, d_pad, k_pad,
                           pad_c=_NEG_BIG)
    op = _estep_clients((False,) * 5, slab.block_n, interpret)
    s0, s1, s2, ll = op(slab.blocks[None], slab.x, slab.w, a, b, c)
    return s0[0, 0, :k], s1[0, :k, :d], s2[0, :k, :d], ll[0, 0, 0]


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
