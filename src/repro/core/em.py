"""Expectation-Maximization for GMMs (weighted, jit-compiled, while_loop
convergence) plus BIC-based model selection — the TrainGMM procedure of
Algorithm 4.1.

This module also owns the **streaming-statistics engine** (DESIGN.md §6):
one generic ``lax.scan``-over-row-chunks reduction (:func:`streaming_reduce`
/ :func:`streaming_map_reduce`) plus the single ``chunk_size is None`` →
full-batch / chunked dispatch (:func:`reduce_rows`). The E-step, the k-means
Lloyd sweeps (``repro.core.kmeans``), the k-means-init label statistics and
the log-likelihood/BIC scoring reductions below all run through it, so the
whole TrainGMM pipeline — init, EM, model selection — has an O(chunk·K)
constant-memory mode.

Every engine entry point also accepts a :class:`repro.data.sources.DataSource`
in the rows position (DESIGN.md §7): sources drive a **host-side block
loop** over ``iter_blocks(chunk_size)`` with jitted per-block statistics
instead of a ``lax.scan`` over a resident reshaped array, so N never has to
be resident at all — the out-of-core mode. The same additivity argument
applies; block sums accumulate in the same order with the same per-block
math, so source-backed fits are bit-reproducible across source types
holding the same rows.

Sample weights make padded/ragged federated client datasets representable as
fixed-shape arrays (weight 0 = padding), which is what lets local training
run under vmap/shard_map — and what lets the engine pad row counts to chunk
boundaries for free (zero-weight rows contribute exactly zero to every
statistic).
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.config import (DEFAULT_SOURCE_CHUNK, ENGINE_BACKENDS,
                               FitConfig, require_array_weights,
                               resolve_backend, resolve_estep_backend,
                               resolve_source_chunk)
from repro.core.gmm import GMM, MATMUL_PRECISION
from repro.data.sources import DataSource, prefetch_blocks


class EMResult(NamedTuple):
    gmm: GMM
    log_likelihood: jax.Array  # final average log-likelihood
    n_iter: jax.Array
    converged: jax.Array


class SufficientStats(NamedTuple):
    """Weighted sufficient statistics of one E-step.

    s0 : (K,)     sum_n w_n r_nk
    s1 : (K, d)   sum_n w_n r_nk x_n
    s2 : (K, d) or (K, d, d)   sum_n w_n r_nk x_n x_n(^T)
    loglik : ()   weighted total log-likelihood
    wsum : ()     total sample weight
    """
    s0: jax.Array
    s1: jax.Array
    s2: jax.Array
    loglik: jax.Array
    wsum: jax.Array


# ----------------------------------------------------------------------
# Streaming-statistics engine (DESIGN.md §6)
# ----------------------------------------------------------------------
# The backend/chunk resolvers and the FitConfig they fold into live in
# ``repro.core.config`` (below this module); re-exported here because this
# module has been their historical public home since PR 1.

ESTEP_BACKENDS = ENGINE_BACKENDS  # historical alias (PR 1 public name)

_require_no_weight = require_array_weights  # historical internal name


def _pad_to_chunks(arrays: Sequence[jax.Array], chunk_size: int):
    """Zero-pad leading axis N to a chunk multiple, reshape to
    (n_chunks, chunk_size, ...). Zero padding is safe because every engine
    statistic weights rows by a sample weight that pads to zero."""
    chunk_size = int(chunk_size)
    if chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    n = arrays[0].shape[0]
    n_chunks = -(-n // chunk_size)
    pad = n_chunks * chunk_size - n
    return tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (n_chunks, chunk_size) + a.shape[1:]) for a in arrays)


# Module-level jitted promote/accumulate for the host block loop: ONE
# dispatch per block (not one per stats leaf), and the trace cache is keyed
# only on the stats pytree structure — never on the block index.

@jax.jit
def _promote_stats(stats):
    return jax.tree.map(
        lambda s: s.astype(jnp.promote_types(s.dtype, jnp.float32)), stats)


@jax.jit
def _acc_stats(acc, stats):
    return jax.tree.map(lambda a, s: a + s.astype(a.dtype), acc, stats)


def _source_map_reduce(block_fn: Callable, source: DataSource,
                       chunk_size: int):
    """Host-side twin of the ``lax.scan`` path for :class:`DataSource` rows.

    ``block_fn(x_block, w_block) -> (stats, per_row)`` with the same
    additive-stats / per-row contract and the same
    accumulate-in-f32-then-cast-back dtype semantics as
    :func:`streaming_map_reduce`. Blocks arrive through
    :func:`repro.data.sources.prefetch_blocks`: every block is padded to
    one static shape with a 0/1 row-weight mask (``w_block``) marking real
    rows, and the next block's host-side work (paging, generation,
    padding, ``jax.device_put``) overlaps device compute on the current
    one. ``block_fn`` must be a module-level jitted function that weights
    every per-row contribution by ``w_block`` — then it compiles exactly
    once per chunk shape, ragged tail included, and padded rows contribute
    exact zeros to every statistic. Accumulation stays strictly in block
    order, so source-backed fits remain bit-identical across source types
    holding the same rows.
    """
    acc = rows_dtypes = None
    rows_parts: list = []
    n_blocks = 0
    for xb, wb in prefetch_blocks(source, chunk_size):
        stats, rows = block_fn(xb, wb)
        if n_blocks == 0:
            rows_dtypes = jax.tree.map(lambda s: s.dtype, stats)
            acc = _promote_stats(stats)
        else:
            acc = _acc_stats(acc, stats)
        rows_parts.append(rows)
        n_blocks += 1
    if n_blocks == 0:
        raise ValueError(f"source yielded no blocks: {source!r}")
    stats = jax.tree.map(lambda a, dt: a.astype(dt), acc, rows_dtypes)
    # Per-row outputs carry the pad rows; concatenate, then trim back to N
    # (padding only ever trails the final block).
    rows = jax.tree.map(
        lambda *parts: jnp.concatenate(parts, axis=0)[:source.num_rows],
        *rows_parts)
    return stats, rows


def streaming_map_reduce(block_fn: Callable, arrays, chunk_size: int,
                         scan_width: int = 1):
    """Scan ``block_fn`` over fixed-size row chunks of ``arrays``.

    ``block_fn(*chunk_arrays) -> (stats, per_row)`` where ``stats`` is an
    additive pytree (summed across chunks; pass ``()`` for map-only) and
    ``per_row`` is a pytree of per-row outputs (stacked across chunks and
    truncated back to N rows; pass ``()`` for reduce-only).

    The working set is one chunk, not N: this is the constant-memory core
    every streaming path shares. Stats accumulate at least in float32
    (f64 stays f64 under x64) and are cast back to ``block_fn``'s output
    dtypes, so callers see the same dtypes as a full-batch call.

    ``scan_width > 1`` runs a **2-level scan**: the scan steps over
    super-chunks of ``scan_width`` chunks, evaluating ``block_fn`` on the
    width axis under ``vmap`` — same O(width·chunk) working set per step,
    but the chunk-level work is exposed to XLA as one batched computation
    instead of a serial carry chain. Per-super-chunk stats are summed over
    the width axis, so reduction *order* differs from ``scan_width=1``
    (f32-rounding-level differences, not bit-identity) — the default
    width of 1 is therefore part of the reproducibility contract.

    ``arrays`` may instead be a single :class:`DataSource`, in which case
    ``block_fn`` receives ``(block, row_mask)`` per call and the reduction
    runs as a host-side prefetching block loop (:func:`_source_map_reduce`)
    instead of a ``lax.scan`` — same contract, no resident N
    (``scan_width`` does not apply: blocks arrive one at a time).
    """
    if isinstance(arrays, DataSource):
        return _source_map_reduce(block_fn, arrays, int(chunk_size))
    n = arrays[0].shape[0]
    chunks = _pad_to_chunks(arrays, chunk_size)
    if scan_width > 1:
        return _two_level_map_reduce(block_fn, chunks, int(scan_width), n)
    stats_shape, _ = jax.eval_shape(block_fn, *(c[0] for c in chunks))
    init = jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.promote_types(s.dtype, jnp.float32)),
        stats_shape)

    def body(carry, chunk):
        stats, rows = block_fn(*chunk)
        carry = jax.tree.map(lambda acc, v: acc + v.astype(acc.dtype),
                             carry, stats)
        return carry, rows

    stats, rows = jax.lax.scan(body, init, chunks)
    stats = jax.tree.map(lambda acc, s: acc.astype(s.dtype),
                         stats, stats_shape)
    rows = jax.tree.map(lambda r: r.reshape((-1,) + r.shape[2:])[:n], rows)
    return stats, rows


def _two_level_map_reduce(block_fn: Callable, chunks, width: int, n: int):
    """scan-of-vmapped-chunks: group the (m, chunk, ...) chunk stack into
    (outer, width, chunk, ...) super-chunks (zero-chunk padding at the end
    — safe for the same weight-0 reason as row padding) and reduce
    ``block_fn`` over the width axis inside each scan step."""
    m = chunks[0].shape[0]
    outer = -(-m // width)
    pad = outer * width - m
    supers = tuple(
        jnp.pad(c, ((0, pad),) + ((0, 0),) * (c.ndim - 1)).reshape(
            (outer, width) + c.shape[1:]) for c in chunks)
    stats_shape, _ = jax.eval_shape(block_fn, *(c[0][0] for c in supers))
    init = jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.promote_types(s.dtype, jnp.float32)),
        stats_shape)

    def body(carry, super_chunk):
        stats, rows = jax.vmap(block_fn)(*super_chunk)
        carry = jax.tree.map(
            lambda acc, v: acc + jnp.sum(v.astype(acc.dtype), axis=0),
            carry, stats)
        return carry, rows

    stats, rows = jax.lax.scan(body, init, supers)
    stats = jax.tree.map(lambda acc, s: acc.astype(s.dtype),
                         stats, stats_shape)
    rows = jax.tree.map(lambda r: r.reshape((-1,) + r.shape[3:])[:n], rows)
    return stats, rows


def streaming_reduce(block_fn: Callable, arrays, chunk_size: int,
                     scan_width: int = 1):
    """Reduce-only :func:`streaming_map_reduce`: sum ``block_fn``'s additive
    pytree over all row chunks (arrays or a :class:`DataSource`)."""
    stats, _ = streaming_map_reduce(lambda *a: (block_fn(*a), ()),
                                    arrays, chunk_size, scan_width)
    return stats


def reduce_rows(block_fn: Callable, arrays,
                chunk_size: Optional[int] = None):
    """THE chunk dispatch (previously copy-pasted across em/dem/fed):
    ``chunk_size is None`` runs one full-batch call, an integer streams
    fixed-size chunks through :func:`streaming_reduce`. A
    :class:`DataSource` in the ``arrays`` position always streams
    (``chunk_size=None`` falls back to :data:`DEFAULT_SOURCE_CHUNK` — a
    source has no full batch to run)."""
    if isinstance(arrays, DataSource):
        return streaming_reduce(block_fn, arrays,
                                resolve_source_chunk(chunk_size))
    if chunk_size is None:
        return block_fn(*arrays)
    return streaming_reduce(block_fn, arrays, chunk_size)


# ----------------------------------------------------------------------
# E / M steps
# ----------------------------------------------------------------------

def _e_step_stats_reference(gmm: GMM, x: jax.Array,
                            w: jax.Array) -> SufficientStats:
    """Pure-jnp E-step: materializes the (N, K) responsibility matrix."""
    lp = gmm.component_log_prob(x) + jnp.log(gmm.weights)[None, :]   # (N, K)
    log_norm = jax.scipy.special.logsumexp(lp, axis=1)               # (N,)
    resp = jnp.exp(lp - log_norm[:, None]) * w[:, None]              # (N, K)
    s0 = jnp.sum(resp, axis=0)                                       # (K,)
    s1 = jnp.matmul(resp.T, x, precision=MATMUL_PRECISION)          # (K, d)
    if gmm.is_diagonal:
        s2 = jnp.matmul(resp.T, x * x, precision=MATMUL_PRECISION)  # (K, d)
    else:
        s2 = jnp.einsum("nk,ni,nj->kij", resp, x, x,
                        precision=MATMUL_PRECISION)                  # (K, d, d)
    loglik = jnp.sum(log_norm * w)
    return SufficientStats(s0, s1, s2, loglik, jnp.sum(w))


def e_step_stats(gmm: GMM, x: jax.Array,
                 sample_weight: Optional[jax.Array] = None,
                 estep_backend: str = "auto",
                 chunk_size: Optional[int] = None,
                 scan_width: int = 1) -> SufficientStats:
    """One E-step: responsibilities -> sufficient statistics.

    This is the communication payload of DEM (each client computes local
    stats; the server psums them) and the compute hot spot. The
    ``estep_backend`` knob dispatches between the pure-jnp reference path
    and the fused Pallas kernel (``repro.kernels.ops.estep_stats``), which
    never materializes the (N, K) responsibility matrix; ``chunk_size``
    streams either backend through the engine in O(chunk·K) memory, so
    this one function is the whole dispatch table for federated callers.
    ``x`` may be a :class:`DataSource` (host-side block loop, §7); sources
    carry no sample weights. ``scan_width > 1`` batches that many chunks
    per scan step on the resident chunked path (2-level scan, see
    :func:`streaming_map_reduce`) — reduction order changes, so the
    default of 1 is part of the reproducibility contract.
    """
    backend = resolve_estep_backend(estep_backend, gmm.is_diagonal)
    if _is_slab(x):
        # rows the loop prepared once for the fused kernel (prepare_rows)
        return e_step_stats_fused(gmm, x, sample_weight)
    if isinstance(x, DataSource):
        _require_no_weight(sample_weight, "e_step_stats over a DataSource")
        block_fn = (_estep_block_fused if backend == "fused"
                    else _estep_block_reference)
        return reduce_rows(lambda xb, wb: block_fn(gmm, xb, wb), x,
                           chunk_size)
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
    if backend == "fused":
        block = lambda xb, wb: e_step_stats_fused(gmm, xb, wb)
    else:
        block = lambda xb, wb: _e_step_stats_reference(gmm, xb, wb)
    if scan_width > 1 and chunk_size is not None:
        return streaming_reduce(block, (x, w), int(chunk_size), scan_width)
    return reduce_rows(block, (x, w), chunk_size)


def e_step_stats_fused(gmm: GMM, x: jax.Array,
                       sample_weight: Optional[jax.Array] = None,
                       interpret: Optional[bool] = None) -> SufficientStats:
    """Kernel-backed E-step (diagonal covariance only): the Pallas
    ``estep_stats`` kernel fuses log-pdf -> softmax -> reductions in VMEM.
    Semantically identical to :func:`e_step_stats`; used on TPU where the
    (N, K) responsibility matrix would otherwise round-trip HBM. ``x`` may
    be the kernel's prepared slab (:func:`prepare_rows`), which carries
    the weights padded; ``sample_weight`` then gives their sum."""
    from repro.kernels import ops  # local import: kernels are optional
    assert gmm.is_diagonal, "fused E-step kernel supports diagonal covariance"
    log_w = jnp.log(gmm.weights)
    if isinstance(x, ops.Slab):
        w = (jnp.ones(x.n, jnp.float32) if sample_weight is None
             else sample_weight)
        stats = ops.estep_stats(x, gmm.means, gmm.covs, log_w,
                                interpret=interpret)
    else:
        w = (jnp.ones(x.shape[0], x.dtype) if sample_weight is None
             else sample_weight)
        stats = ops.estep_stats(x, gmm.means, gmm.covs, log_w, w,
                                interpret=interpret)
    return SufficientStats(*stats, jnp.sum(w))


def _is_slab(x) -> bool:
    if isinstance(x, (jax.Array, DataSource)):
        return False
    from repro.kernels import ops  # local import: kernels are optional
    return isinstance(x, ops.Slab)


def prepare_rows(x: jax.Array, sample_weight: Optional[jax.Array],
                 backend: str, chunk_size: Optional[int]):
    """What a loop over resident rows hands its fused-kernel calls: the
    kernels' padded slab of ``x`` (``repro.kernels.ops.prepare``), built
    once where the resolved ``backend`` is fused and the rows run as one
    batch; ``x`` itself anywhere else (reference backend, chunked rows),
    where each call pads its own block. Build it before ``lax.while_loop``
    and let the body close over it: a carried slab would be selected
    whole on every iteration of a vmapped loop."""
    if backend != "fused" or chunk_size is not None:
        return x
    from repro.kernels import ops  # local import: kernels are optional
    return ops.prepare(x, sample_weight)


def prepared_bytes(n: int, d: int, backend: str, chunk_size: Optional[int],
                   weights: bool = True) -> Optional[int]:
    """Device bytes of the slab :func:`prepare_rows` builds for ``(n, d)``
    rows (``weights``: with the E-step's weight column; the k-means
    assignment reads the rows alone); None where it builds none."""
    if backend != "fused" or chunk_size is not None:
        return None
    from repro.kernels import ops  # local import: kernels are optional
    return ops.slab_bytes(n, d, weights)


def slab_row_block(backend: str, chunk_size: Optional[int]) -> Optional[int]:
    """Rows of the blocks the fused E-step computes over the slab
    :func:`prepare_rows` builds: it computes a client's blocks up to its
    last weighted row and skips the rest (``repro.kernels.ops.Slab``).
    None where no slab is built."""
    if backend != "fused" or chunk_size is not None:
        return None
    from repro.kernels import ops  # local import: kernels are optional
    return ops.BLOCK_N


def computed_lanes(d: int, backend: str) -> int:
    """Feature width an engine op computes over on a resolved ``backend``:
    the fused kernels pad the ``d`` features to the lane width
    (``repro.kernels.ops.padded_lanes``); the reference path computes
    ``d``."""
    if backend != "fused":
        return d
    from repro.kernels import ops  # local import: kernels are optional
    return ops.padded_lanes(d)


# Per-block statistics for the DataSource host loop. Module-level jitted so
# every pass over a source hits the trace cache — exactly ONE block shape
# exists per stream (prefetch_blocks pads the ragged tail to the full chunk
# and hands each block a 0/1 row mask ``wb``); parameters (gmm) are traced
# arguments, never closure constants.

@jax.jit
def _estep_block_reference(gmm: GMM, xb: jax.Array,
                           wb: jax.Array) -> SufficientStats:
    return _e_step_stats_reference(gmm, xb, wb)


@jax.jit
def _estep_block_fused(gmm: GMM, xb: jax.Array,
                       wb: jax.Array) -> SufficientStats:
    return e_step_stats_fused(gmm, xb, wb)


def e_step_stats_chunked(gmm: GMM, x: jax.Array,
                         sample_weight: Optional[jax.Array] = None,
                         chunk_size: int = 4096,
                         estep_backend: str = "auto") -> SufficientStats:
    """Constant-memory E-step: ``lax.scan`` over fixed-size row chunks.

    ``SufficientStats`` is additive in N, so the full-batch statistics are
    the chunk-wise sum — the working set is one (chunk_size, K) block
    instead of the whole (N, K) responsibility matrix (see
    :func:`streaming_reduce` for padding/accumulation semantics). Caveat:
    the *fused* backend computes each chunk in f32 regardless (the kernel
    packs params as f32), so f64 precision is only preserved end-to-end on
    the reference backend.
    """
    return e_step_stats(gmm, x, sample_weight, estep_backend,
                        chunk_size=int(chunk_size))


def m_step(stats: SufficientStats, reg_covar: float = 1e-6) -> GMM:
    """M-step from (possibly aggregated) sufficient statistics."""
    s0 = jnp.maximum(stats.s0, 1e-10)
    weights = stats.s0 / jnp.maximum(stats.wsum, 1e-12)
    weights = weights / jnp.sum(weights)
    means = stats.s1 / s0[:, None]
    if stats.s2.ndim == 2:  # diagonal
        covs = stats.s2 / s0[:, None] - means * means
        covs = jnp.maximum(covs, 0.0) + reg_covar
    else:
        outer = jnp.einsum("ki,kj->kij", means, means)
        covs = stats.s2 / s0[:, None, None] - outer
        # robustness against component collapse (few near-colinear points):
        # symmetrize, sanitize non-finite, floor the diagonal — the EM
        # iteration then reassigns mass instead of diverging to NaN
        covs = 0.5 * (covs + jnp.swapaxes(covs, -1, -2))
        covs = jnp.where(jnp.isfinite(covs), covs, 0.0)
        d = means.shape[1]
        eye = jnp.eye(d, dtype=means.dtype)[None]
        covs = covs + reg_covar * eye
        diag = jnp.maximum(jnp.diagonal(covs, axis1=-2, axis2=-1), reg_covar)
        covs = covs * (1.0 - eye) + diag[..., None] * eye
    means = jnp.where(jnp.isfinite(means), means, 0.0)
    return GMM(weights, means, covs)


def em_step(gmm: GMM, x: jax.Array, sample_weight: Optional[jax.Array] = None,
            reg_covar: float = 1e-6, estep_backend: str = "auto",
            chunk_size: Optional[int] = None) -> tuple[GMM, jax.Array]:
    """One full EM iteration. Returns (new_gmm, avg_loglik_of_old_gmm).

    ``chunk_size=None`` runs the whole batch in one E-step; an integer
    streams it through the engine in bounded memory.
    """
    stats = e_step_stats(gmm, x, sample_weight, estep_backend, chunk_size)
    avg_ll = stats.loglik / jnp.maximum(stats.wsum, 1e-12)
    return m_step(stats, reg_covar), avg_ll


# ----------------------------------------------------------------------
# Streaming scoring: log-likelihood and BIC without the (N, K) matrix
# ----------------------------------------------------------------------

def _log_prob_block(gmm: GMM, xb: jax.Array, backend: str) -> jax.Array:
    """Mixture log density of one row block, (B, d) -> (B,). The fused
    backend routes the (B, K) per-component density through the Pallas
    ``gmm_logpdf`` kernel (diagonal only); reference uses ``GMM.log_prob``."""
    if backend == "fused":
        from repro.kernels import ops  # local import: kernels are optional
        lp = ops.gmm_logpdf(xb, gmm.means, gmm.covs, jnp.log(gmm.weights))
        return jax.scipy.special.logsumexp(lp, axis=1).astype(xb.dtype)
    return gmm.log_prob(xb)


@partial(jax.jit, static_argnames=("backend",))
def _log_prob_block_jit(gmm: GMM, xb: jax.Array, backend: str) -> jax.Array:
    return _log_prob_block(gmm, xb, backend)


@partial(jax.jit, static_argnames=("backend",))
def _score_block(gmm: GMM, xb: jax.Array, wb: jax.Array, backend: str):
    lp = _log_prob_block(gmm, xb, backend)
    return jnp.sum(lp * wb), jnp.sum(wb)


def log_prob_chunked(gmm: GMM, x: jax.Array,
                     chunk_size: Optional[int] = 4096,
                     backend: str = "auto") -> jax.Array:
    """``GMM.log_prob`` in fixed-size row chunks -> (N,).

    Peak working set is one (chunk_size, K) density block instead of the
    full (N, K) matrix — what the anomaly-detection scorer needs to run
    over datasets that don't fit the full-batch path. ``chunk_size=None``
    runs one full-batch block (same backend resolution), so callers can
    delegate unconditionally like every other engine entry point. Accepts a
    :class:`DataSource` (the per-row *output* is still O(N), but only 4
    bytes a row — the (N, K) block never exists).

    Every path runs the ONE jitted block (``_log_prob_block_jit``), so
    chunked, full-batch and the serving engine's padded-slab scores agree
    to a few float32 ulps; XLA does not promise one accumulation order
    across batch shapes, so they need not share every bit.
    """
    backend = resolve_backend(backend, fused_supported=gmm.is_diagonal)
    if isinstance(x, DataSource):
        _, lp = streaming_map_reduce(
            lambda xb, wb: ((), _log_prob_block_jit(gmm, xb, backend)), x,
            resolve_source_chunk(chunk_size))
        return lp
    if chunk_size is None:
        return _log_prob_block_jit(gmm, x, backend)
    _, lp = streaming_map_reduce(
        lambda xb: ((), _log_prob_block_jit(gmm, xb, backend)), (x,),
        chunk_size)
    return lp


def _score_sums(gmm: GMM, x: jax.Array, sample_weight: Optional[jax.Array],
                chunk_size: Optional[int], backend: str):
    """(sum_n w_n log p(x_n), sum_n w_n) through the engine."""
    backend = resolve_backend(backend, fused_supported=gmm.is_diagonal)
    if isinstance(x, DataSource):
        _require_no_weight(sample_weight, "scoring over a DataSource")
        return reduce_rows(lambda xb, wb: _score_block(gmm, xb, wb, backend),
                           x, chunk_size)
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight

    def block(xb, wb):
        lp = _log_prob_block(gmm, xb, backend)
        return jnp.sum(lp * wb), jnp.sum(wb)

    return reduce_rows(block, (x, w), chunk_size)


def score_streaming(gmm: GMM, x: jax.Array,
                    sample_weight: Optional[jax.Array] = None,
                    chunk_size: Optional[int] = 4096,
                    backend: str = "auto") -> jax.Array:
    """Average log-likelihood (the paper's fitness score, Eq. 2) in
    O(chunk·K) memory. Equals ``GMM.score`` up to float-summation order."""
    total, wsum = _score_sums(gmm, x, sample_weight, chunk_size, backend)
    return total / jnp.maximum(wsum, 1e-12)


def bic_streaming(gmm: GMM, x: jax.Array,
                  sample_weight: Optional[jax.Array] = None,
                  chunk_size: Optional[int] = 4096,
                  backend: str = "auto") -> jax.Array:
    """Bayesian Information Criterion in O(chunk·K) memory (lower is
    better). Equals ``GMM.bic`` up to float-summation order; this is what
    makes BIC model selection over candidate K constant-memory."""
    total, wsum = _score_sums(gmm, x, sample_weight, chunk_size, backend)
    return gmm.n_free_params() * jnp.log(wsum) - 2.0 * total


# ----------------------------------------------------------------------
# Initialization
# ----------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "covariance_type", "chunk_size"))
def label_stats(x: jax.Array, assignments: jax.Array, k: int,
                sample_weight: Optional[jax.Array] = None,
                covariance_type: str = "diag",
                chunk_size: Optional[int] = None) -> SufficientStats:
    """Hard-assignment sufficient statistics via weighted one-hot matmuls
    — per-cluster sums as ``oh.T @ xb`` instead of ``segment_sum`` scatter
    adds (an order of magnitude faster on the CPU backend), with
    ``chunk_size`` bounding the row working set to one (chunk, K) block.

    Resident arrays only (``assignments`` is row-aligned with ``x``); the
    out-of-core init fuses labelling into the final assignment sweep
    instead (``repro.core.kmeans.kmeans_label_block``), so no (N,) label
    vector is ever needed on the source path. Jitted at module level so
    repeated init calls at one (n, k) shape trace once.
    """
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight

    def block(xb, wb, ab):
        cols = jnp.arange(k, dtype=ab.dtype)[None, :]
        oh = (ab[:, None] == cols).astype(xb.dtype) * wb[:, None]
        s0 = jnp.sum(oh, axis=0)
        s1 = jnp.matmul(oh.T, xb, precision=MATMUL_PRECISION)
        if covariance_type == "diag":
            s2 = jnp.matmul(oh.T, xb * xb, precision=MATMUL_PRECISION)
        else:
            s2 = jnp.einsum("nk,ni,nj->kij", oh, xb, xb,
                            precision=MATMUL_PRECISION)
        return SufficientStats(s0, s1, s2, jnp.zeros((), xb.dtype),
                               jnp.sum(wb))

    return reduce_rows(block, (x, w, assignments), chunk_size)


def init_from_kmeans(key: jax.Array, x: jax.Array, k: int,
                     sample_weight: Optional[jax.Array] = None,
                     covariance_type: str = "diag",
                     reg_covar: float = 1e-6,
                     chunk_size: Optional[int] = None,
                     assign_backend: str = "auto") -> GMM:
    """sklearn-style init: k-means labels -> label stats -> M-step.

    With ``chunk_size`` set, both the Lloyd iterations (chunked k-means,
    see ``repro.core.kmeans``) and the label statistics stream in
    O(chunk·K) memory, closing the init leg of the constant-memory
    pipeline. A :class:`DataSource` runs fully out-of-core: streamed
    k-means++ seeding, host-loop Lloyd sweeps, and label statistics fused
    into a final assignment pass (no (N,) assignment vector ever exists).
    """
    # Local import: this module hosts the engine that kmeans.py builds on.
    from repro.core.kmeans import (kmeans_label_block, kmeans_multi,
                                   kmeans_multi_source)
    if isinstance(x, DataSource):
        _require_no_weight(sample_weight, "init_from_kmeans over a DataSource")
        cs = resolve_source_chunk(chunk_size)
        res = kmeans_multi_source(key, x, k, max_iter=50, chunk_size=cs,
                                  assign_backend=assign_backend)
        backend = resolve_backend(assign_backend)
        stats = streaming_reduce(
            lambda xb, wb: kmeans_label_block(res.centers, xb, wb,
                                              covariance_type, backend),
            x, cs)
        return m_step(stats, reg_covar)
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
    res = kmeans_multi(key, x, k, sample_weight=w, max_iter=50,
                       chunk_size=chunk_size, assign_backend=assign_backend)
    stats = label_stats(x, res.assignments, k, w, covariance_type, chunk_size)
    return m_step(stats, reg_covar)


def init_from_means(means: jax.Array, x: jax.Array,
                    sample_weight: Optional[jax.Array] = None,
                    covariance_type: str = "diag",
                    reg_covar: float = 1e-6,
                    chunk_size: Optional[int] = None) -> GMM:
    """Init with given centers, uniform weights, data-variance covariances.

    Used by the DEM baselines, where the server proposes centers without
    seeing client data. Accepts a :class:`DataSource` (streamed one-pass
    moments at ``chunk_size`` granularity; the variance uses E[x²]−E[x]²,
    clamped at zero, instead of the resident two-pass form). On resident
    arrays ``chunk_size`` is ignored — the moments are already O(d).
    """
    k, d = means.shape
    if isinstance(x, DataSource):
        _require_no_weight(sample_weight, "init_from_means over a DataSource")
        s, ss, cnt = reduce_rows(_moments_block, x, chunk_size)
        wsum = jnp.maximum(cnt, 1e-12)
        mean = s / wsum
        var = jnp.maximum(ss / wsum - mean * mean, 0.0) + reg_covar
        weights = jnp.full((k,), 1.0 / k, means.dtype)
        if covariance_type == "diag":
            covs = jnp.broadcast_to(var, (k, d))
        else:
            covs = jnp.broadcast_to(jnp.diag(var), (k, d, d))
        return GMM(weights, means, covs)
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    mean = jnp.sum(x * w[:, None], axis=0) / wsum
    var = jnp.sum((x - mean) ** 2 * w[:, None], axis=0) / wsum + reg_covar
    weights = jnp.full((k,), 1.0 / k, x.dtype)
    if covariance_type == "diag":
        covs = jnp.broadcast_to(var, (k, d))
    else:
        covs = jnp.broadcast_to(jnp.diag(var), (k, d, d))
    return GMM(weights, means, covs)


@partial(jax.jit, static_argnames=("mesh", "axis", "covariance_type"))
def init_from_means_sharded(means: jax.Array, client_data: jax.Array,
                            client_weights: jax.Array, *, mesh,
                            axis: str = "data",
                            covariance_type: str = "diag",
                            reg_covar: float = 1e-6) -> GMM:
    """:func:`init_from_means` over ``(C, N, d)`` clients sharded on the
    ``axis`` of ``mesh``: each shard reduces its own rows to their weight,
    sum and squared deviations from the shard's own mean, and one ``psum``
    combines them (Chan et al.'s pairwise update: the deviations of the
    shards' means from the whole mean add the between-shard part). The
    same variance as the resident two-pass form up to rounding; the
    returned model is replicated over the mesh."""
    k, d = means.shape

    def shard_fn(x_s, w_s):
        x = x_s.reshape(-1, d)
        w = w_s.reshape(-1)
        n = jnp.sum(w)
        s1 = jnp.sum(x * w[:, None], axis=0)
        mean = s1 / jnp.maximum(n, 1e-12)
        m2 = jnp.sum((x - mean) ** 2 * w[:, None], axis=0)
        # === the init's one all-reduce ===
        return jax.lax.psum((n, s1, m2, s1 * mean), axis)

    spec = P(axis)
    n, s1, m2, between = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(spec, spec), out_specs=P(),
        check_vma=False)(client_data, client_weights)
    wsum = jnp.maximum(n, 1e-12)
    mean = s1 / wsum
    var = (m2 + between - s1 * mean) / wsum + reg_covar
    weights = jnp.full((k,), 1.0 / k, client_data.dtype)
    if covariance_type == "diag":
        covs = jnp.broadcast_to(var, (k, d))
    else:
        covs = jnp.broadcast_to(jnp.diag(var), (k, d, d))
    return GMM(weights, means, covs)


@jax.jit
def _moments_block(xb: jax.Array, wb: jax.Array):
    """(Σ w x, Σ w x², Σ w) of one block — streamed data moments (``wb`` is
    the 0/1 pad mask, so padded rows count for nothing)."""
    return (jnp.sum(xb * wb[:, None], axis=0),
            jnp.sum(xb * xb * wb[:, None], axis=0), jnp.sum(wb))


# ----------------------------------------------------------------------
# Full EM fit
# ----------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_iter", "estep_backend", "chunk_size"))
def _em_loop(gmm0: GMM, x: jax.Array, w: jax.Array, tol: float,
             reg_covar: float, max_iter: int, estep_backend: str = "auto",
             chunk_size: Optional[int] = None):
    # the fused kernel's slab, padded once for every iteration
    rows = prepare_rows(
        x, w, resolve_estep_backend(estep_backend, gmm0.is_diagonal),
        chunk_size)

    def cond(state):
        _, prev_ll, ll, it = state
        return jnp.logical_and(it < max_iter, jnp.abs(ll - prev_ll) > tol)

    def body(state):
        gmm, _, ll, it = state
        new_gmm, avg_ll = em_step(gmm, rows, w, reg_covar, estep_backend,
                                  chunk_size)
        return new_gmm, ll, avg_ll, it + 1

    neg_inf = jnp.array(-jnp.inf, x.dtype)
    # Bootstrap: one step to get an initial loglik.
    gmm1, ll0 = em_step(gmm0, rows, w, reg_covar, estep_backend, chunk_size)
    state = (gmm1, neg_inf, ll0, jnp.array(1))
    gmm, prev_ll, ll, it = jax.lax.while_loop(cond, body, state)
    converged = jnp.abs(ll - prev_ll) <= tol
    return gmm, ll, it, converged


_m_step_jit = jax.jit(m_step)


def host_em_loop(step: Callable, gmm0: GMM, tol: float, max_iter: int):
    """The host-side EM convergence loop shared by every out-of-core
    trainer (:func:`fit_gmm` over a source, ``dem_from_sources``): run a
    bootstrap ``step(gmm) -> (new_gmm, avg_ll)``, then iterate while the
    avg-loglik delta exceeds ``tol``. State transitions, the bootstrap
    round and the tolerance test mirror the jitted resident loops
    (:func:`_em_loop`, ``_dem_loop``) exactly, so resident and source
    paths converge on the same iteration sequence — keep all three in
    lock-step. Returns ``(gmm, avg_ll, n_iter, converged)``."""
    tol = float(tol)
    gmm, ll = step(gmm0)
    prev_ll, it = float("-inf"), 1
    while it < max_iter and abs(ll - prev_ll) > tol:
        new_gmm, avg_ll = step(gmm)
        gmm, prev_ll, ll, it = new_gmm, ll, avg_ll, it + 1
    converged = abs(ll - prev_ll) <= tol
    dt = gmm.means.dtype
    return gmm, jnp.asarray(ll, dt), jnp.asarray(it), jnp.asarray(converged)


def _em_loop_source(gmm0: GMM, source: DataSource, tol: float,
                    reg_covar: float, max_iter: int, estep_backend: str,
                    chunk_size: int):
    """Out-of-core twin of :func:`_em_loop`: the convergence loop runs on
    the host (a source cannot live inside jit) while every per-block E-step
    and the M-step stay jitted."""
    backend = resolve_estep_backend(estep_backend, gmm0.is_diagonal)
    block_fn = (_estep_block_fused if backend == "fused"
                else _estep_block_reference)

    def step(gmm):
        stats = streaming_reduce(lambda xb, wb: block_fn(gmm, xb, wb), source,
                                 chunk_size)
        avg_ll = float(stats.loglik / jnp.maximum(stats.wsum, 1e-12))
        return _m_step_jit(stats, reg_covar), avg_ll

    return host_em_loop(step, gmm0, tol, max_iter)


def fit_gmm_cfg(key: jax.Array, x, k: int, config: FitConfig,
                sample_weight: Optional[jax.Array] = None,
                init_gmm: Optional[GMM] = None) -> EMResult:
    """Train a GMM with EM until the avg-loglik delta drops below the
    config's ``tol`` (the paper's convergence criterion, 1e-3).

    The cfg-core trainer behind both :func:`fit_gmm` and
    ``repro.api.GMMEstimator``: every knob arrives pre-validated in one
    :class:`FitConfig`, resolved exactly once here. ``config.backend``
    selects the E-step implementation (DESIGN.md §6); an integer
    ``config.chunk_size`` streams the init (k-means + label stats) *and*
    every E-step in bounded memory. The k-means assignment backend stays
    "auto" (kernel on TPU, reference elsewhere) rather than following the
    E-step backend: an explicitly requested fused E-step off-TPU is a
    parity-testing configuration, and interpret-mode Lloyd sweeps would
    make it unusably slow.

    ``x`` may be a :class:`DataSource` (DESIGN.md §7): init, every E-step
    and convergence then run as host-driven block loops with an
    O(chunk·K) working set independent of N — true out-of-core training
    (``chunk_size="auto"`` streams at :data:`DEFAULT_SOURCE_CHUNK`).
    """
    # Validate eagerly: _em_loop sees the knob as a static jit arg and a
    # typo'd value would otherwise surface as an opaque trace-time error.
    config.resolved_estep(config.is_diagonal if init_gmm is None
                          else init_gmm.is_diagonal)
    tol = config.resolve_tol("em")
    max_iter = config.resolve_max_iter("em")
    if isinstance(x, DataSource):
        require_array_weights(sample_weight, "fit_gmm over a DataSource")
        cs = config.resolve_chunk(source=True)
        if init_gmm is None:
            init_gmm = init_from_kmeans(
                key, x, k, covariance_type=config.covariance_type,
                reg_covar=config.reg_covar, chunk_size=cs)
        gmm, ll, it, converged = _em_loop_source(
            init_gmm, x, tol, config.reg_covar, max_iter,
            config.backend, cs)
        return EMResult(gmm, ll, it, converged)
    cs = config.resolve_chunk(source=False)
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
    if init_gmm is None:
        init_gmm = init_from_kmeans(key, x, k, w, config.covariance_type,
                                    config.reg_covar, chunk_size=cs)
    gmm, ll, it, converged = _em_loop(
        init_gmm, x, w, jnp.asarray(tol, x.dtype), config.reg_covar,
        max_iter, config.backend, cs)
    return EMResult(gmm, ll, it, converged)


def fit_prepared_bytes(n: int, d: int, config: FitConfig) -> Optional[int]:
    """Device bytes of the kernel slabs one resident :func:`fit_gmm_cfg`
    of ``(n, d)`` rows builds once (:func:`prepare_rows`): the EM loop's,
    with its weight column, and those of the k-means init, whose Lloyd
    loops assign on the "auto" backend: the rows (one copy with the EM
    loop's, as the compiler merges equal pads) and, past
    ``repro.core.kmeans.SEED_ROWS`` rows, the restarts' row subsample,
    which runs as one batch whatever the chunk size. None where every
    kernel call gets raw rows."""
    from repro.core.kmeans import SEED_ROWS  # kmeans builds on this module
    chunk = config.resolve_chunk(source=False)
    assign = resolve_backend("auto")
    rows = prepared_bytes(n, d, config.resolved_estep(), chunk)
    if rows is None:
        rows = prepared_bytes(n, d, assign, chunk, weights=False)
    seed = (prepared_bytes(SEED_ROWS, d, assign, None, weights=False)
            if n > SEED_ROWS else None)
    if rows is None and seed is None:
        return None
    return (rows or 0) + (seed or 0)


def fit_gmm(key: jax.Array, x: jax.Array, k: int,
            sample_weight: Optional[jax.Array] = None,
            covariance_type: str = "diag",
            max_iter: int = 200, tol: float = 1e-3,
            reg_covar: float = 1e-6,
            init_gmm: Optional[GMM] = None,
            estep_backend: str = "auto",
            chunk_size: Optional[int] = None) -> EMResult:
    """Legacy keyword surface of :func:`fit_gmm_cfg` (internal; prefer
    ``repro.api.GMMEstimator``): folds the loose knobs into one validated
    :class:`FitConfig` — ``chunk_size=None`` keeps its historical meaning
    (full batch resident / :data:`DEFAULT_SOURCE_CHUNK` out-of-core) by
    mapping to ``chunk_size="auto"``."""
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter)
    return fit_gmm_cfg(key, x, k, cfg, sample_weight, init_gmm)


def fit_gmm_streaming(key: jax.Array, x: jax.Array, k: int,
                      sample_weight: Optional[jax.Array] = None,
                      covariance_type: str = "diag",
                      max_iter: int = 200, tol: float = 1e-3,
                      reg_covar: float = 1e-6,
                      init_gmm: Optional[GMM] = None,
                      estep_backend: str = "auto",
                      chunk_size: int = 4096) -> EMResult:
    """Deprecated: ``repro.api.GMMEstimator`` with an integer
    ``FitConfig.chunk_size`` is the same all-streaming fit. This shim
    forwards to the facade (bit-identical result) and will be removed."""
    warnings.warn(
        "fit_gmm_streaming is deprecated; use repro.api.GMMEstimator(k, "
        "chunk_size=<int>).fit(x) — same engine, same bits",
        DeprecationWarning, stacklevel=2)
    from repro.api import GMMEstimator  # facade sits above core; lazy
    est = GMMEstimator(k, config=FitConfig.from_legacy(
        backend=estep_backend, chunk_size=int(chunk_size),
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter))
    est.fit(x, sample_weight=sample_weight, init_gmm=init_gmm, key=key)
    return est.result_


def fit_gmm_bic_cfg(key: jax.Array, x, k_candidates: Sequence[int],
                    config: FitConfig,
                    sample_weight: Optional[jax.Array] = None
                    ) -> tuple[EMResult, dict[int, float]]:
    """TrainGMM of Algorithm 4.1: fit every K in the candidate range, return
    the fit minimizing BIC (plus all BIC scores).

    With an integer ``config.chunk_size`` the per-candidate scoring runs
    through :func:`bic_streaming`, so model selection never materializes
    the (N, K) log-prob matrix the full-batch ``GMM.bic`` builds. With a
    :class:`DataSource` the whole selection — every candidate's init, EM
    and BIC score — runs out-of-core.
    """
    score_chunk = config.resolve_chunk(isinstance(x, DataSource))
    best, best_bic, bics = None, jnp.inf, {}
    for i, k in enumerate(k_candidates):
        res = fit_gmm_cfg(jax.random.fold_in(key, i), x, k, config,
                          sample_weight)
        # scoring backend stays "auto" (kernel on TPU, reference elsewhere)
        # rather than following config.backend, for the same reason the
        # fit pins the k-means assign backend: an explicit fused E-step
        # off-TPU is a parity-testing configuration, and interpret-mode
        # scoring of every candidate K would crawl.
        b = float(bic_streaming(res.gmm, x, sample_weight,
                                chunk_size=score_chunk))
        bics[k] = b
        if b < best_bic:
            best, best_bic = res, b
    return best, bics


def fit_gmm_bic(key: jax.Array, x: jax.Array, k_candidates: Sequence[int],
                sample_weight: Optional[jax.Array] = None,
                covariance_type: str = "diag",
                max_iter: int = 200, tol: float = 1e-3,
                reg_covar: float = 1e-6,
                estep_backend: str = "auto",
                chunk_size: Optional[int] = None) -> tuple[EMResult,
                                                           dict[int, float]]:
    """Legacy keyword surface of :func:`fit_gmm_bic_cfg` (internal; prefer
    ``repro.api.GMMEstimator`` with ``k_candidates``)."""
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter)
    return fit_gmm_bic_cfg(key, x, k_candidates, cfg, sample_weight)
