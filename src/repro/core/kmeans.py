"""K-means in JAX: k-means++ seeding, weighted Lloyd iterations, and the
one-shot federated k-means of Dennis et al. '21 (paper ref [7]) used both
standalone and as DEM init 3.

Lloyd sweeps run on the streaming-statistics engine (``repro.core.em``,
DESIGN.md §6): each sweep reduces (counts, sums, inertia) sufficient
statistics over row blocks — never an (N, K) one-hot — and with
``chunk_size`` set the distance block itself shrinks to (chunk_size, K).
Per-block assignment dispatches through the ``kmeans_assign`` Pallas kernel
on TPU (``assign_backend="auto"``) and the matmul-identity reference
elsewhere.

Out-of-core data runs through the source twins (DESIGN.md §7):
``kmeans_plusplus_streaming`` (Gumbel-max seeding over blocks),
``kmeans_source``/``kmeans_multi_source`` (host-driven Lloyd loops) and
``federated_kmeans_from_sources`` — none of which ever hold an (N, ·)
array.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.config import (FitConfig, is_source_list,
                               require_array_weights, resolve_backend,
                               resolve_source_chunk)
from repro.core.em import (SufficientStats, prepare_rows, reduce_rows,
                           streaming_map_reduce, streaming_reduce)
from repro.core.gmm import MATMUL_PRECISION
from repro.data.sources import DataSource, prefetch_blocks

# Rows the k-means++ seeding pass works from when the dataset is larger:
# seeding is O(k · N_pool · d) with a k-round categorical over an
# (N_pool,)-logit vector, and a uniform subsample this size seeds planted
# mixtures indistinguishably from the full pass at a fraction of the cost
# (the Lloyd iterations that follow see every row regardless).
SEED_ROWS = 16384

# Lockstep Lloyd sweeps every restart runs before kmeans_multi prunes to
# the best seed (see kmeans_multi): enough for inertia to separate good
# seedings from bad on anything EM-initializable, while bad restarts never
# get to drag a vmapped while_loop through dozens of straggler iterations.
PILOT_ITERS = 3

# Full-data Lloyd budget for kmeans_multi's refine stage beyond SEED_ROWS
# rows: the winner first converges on the seed subsample (cheap sweeps),
# then polishes on the full data — at 100k rows a full sweep costs ~9ms
# on the 1-core CPU backend, so an unbounded full-data while_loop is what
# made init_from_kmeans a 6.3s outlier.
REFINE_ITERS = 10


class KMeansResult(NamedTuple):
    centers: jax.Array        # (K, d)
    assignments: jax.Array    # (N,); None on out-of-core (DataSource) runs
    inertia: jax.Array        # ()
    n_iter: jax.Array         # ()
    cluster_sizes: jax.Array  # (K,) sum of sample weights per cluster


def _sq_dists(x: jax.Array, centers: jax.Array) -> jax.Array:
    """Squared euclidean distances (N, K) via the matmul identity."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)           # (N, 1)
    c2 = jnp.sum(centers * centers, axis=1)[None, :]     # (1, K)
    xc = jnp.matmul(x, centers.T, precision=MATMUL_PRECISION)
    return jnp.maximum(x2 - 2.0 * xc + c2, 0.0)


def _assign_block(xb: jax.Array, centers: jax.Array,
                  backend: str) -> tuple[jax.Array, jax.Array]:
    """Nearest-center assignment of one row block -> ((B,) int32, (B,) d2).
    ``fused`` routes through the Pallas ``kmeans_assign`` kernel (``xb``
    may be its prepared slab, ``repro.core.em.prepare_rows``), reference
    through the matmul identity; both share the §3 contraction."""
    if backend == "fused":
        from repro.kernels import ops  # local import: kernels are optional
        return ops.kmeans_assign(xb, centers)
    dists = _sq_dists(xb, centers)
    return (jnp.argmin(dists, axis=1).astype(jnp.int32),
            jnp.min(dists, axis=1))


def _labels_onehot(idx: jax.Array, k: int, wb: jax.Array,
                   dtype) -> jax.Array:
    """Weighted one-hot (B, K) of an assignment vector. Per-cluster sums
    then become matmuls (``oh.T @ xb``) instead of ``segment_sum`` scatter
    adds — the scatter path costs ~13ms per 100k-row sweep on a 1-core
    CPU backend, the matmul path ~1ms, and Lloyd runs one sweep per
    iteration (this was most of the 6.3s init outlier)."""
    cols = jnp.arange(k, dtype=idx.dtype)[None, :]
    return (idx[:, None] == cols).astype(dtype) * wb[:, None]


def _sweep_block(xb: jax.Array, wb: jax.Array, centers: jax.Array,
                 backend: str, ab=None):
    """Weighted Lloyd-sweep sufficient statistics of one block:
    (counts (K,), sums (K, d), inertia ()). The assignment reads ``ab``,
    the block's prepared slab, where the caller has one; the sums read
    the rows themselves."""
    k = centers.shape[0]
    idx, d2 = _assign_block(xb if ab is None else ab, centers, backend)
    oh = _labels_onehot(idx, k, wb, xb.dtype)
    return (jnp.sum(oh, axis=0),
            jnp.matmul(oh.T, xb, precision=MATMUL_PRECISION),
            jnp.sum(d2 * wb))


def kmeans_plusplus(key: jax.Array, x: jax.Array, k: int,
                    sample_weight: Optional[jax.Array] = None) -> jax.Array:
    """k-means++ seeding -> (k, d). Supports zero-weighted (padded) rows."""
    n = x.shape[0]
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
    key, sub = jax.random.split(key)
    first = jax.random.categorical(sub, jnp.log(jnp.maximum(w, 1e-30)))
    centers0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    min_d0 = jnp.sum((x - x[first]) ** 2, axis=1)

    def body(i, carry):
        centers, min_d, key = carry
        key, sub = jax.random.split(key)
        probs = jnp.maximum(min_d * w, 1e-30)
        idx = jax.random.categorical(sub, jnp.log(probs))
        c = x[idx]
        centers = centers.at[i].set(c)
        min_d = jnp.minimum(min_d, jnp.sum((x - c) ** 2, axis=1))
        return centers, min_d, key

    centers, _, _ = jax.lax.fori_loop(1, k, body, (centers0, min_d0, key))
    return centers


def _seed_centers(key: jax.Array, x: jax.Array, k: int, w: jax.Array,
                  seed_rows: int) -> jax.Array:
    """k-means++ over a uniform row subsample once N exceeds ``seed_rows``
    (sampled rows keep their weights); the full pass below that. Seeding
    was measured at >100ms per restart on a 100k-row batch — almost all of
    it the k categorical draws over (N,) logits — and the Lloyd iterations
    wash out any subsampling noise in the seed."""
    n = x.shape[0]
    if n <= seed_rows:
        return kmeans_plusplus(key, x, k, w)
    key, sub = jax.random.split(key)
    idx = jax.random.randint(sub, (seed_rows,), 0, n)
    return kmeans_plusplus(key, x[idx], k, w[idx])


@partial(jax.jit, static_argnames=("k", "max_iter", "chunk_size",
                                   "assign_backend", "seed_rows"))
def kmeans(key: jax.Array, x: jax.Array, k: int,
           sample_weight: Optional[jax.Array] = None,
           max_iter: int = 100, tol: float = 1e-4,
           chunk_size: Optional[int] = None,
           assign_backend: str = "auto",
           init_centers: Optional[jax.Array] = None,
           seed_rows: int = SEED_ROWS) -> KMeansResult:
    """Weighted Lloyd's algorithm with k-means++ init.

    Every sweep accumulates (counts (K,), sums (K, d), inertia) sufficient
    statistics per assignment block — no (N, K) one-hot. ``chunk_size=None``
    assigns the whole batch at once (one (N, K) distance block on the
    reference backend); an integer scans (chunk_size, d) slices so the peak
    working set is O(chunk_size·K). The returned assignments, inertia and
    cluster sizes are recomputed against the *returned* centers (a final
    sweep), not the pre-update centers of the last Lloyd iteration.

    Beyond ``seed_rows`` rows the k-means++ pass seeds from a uniform row
    subsample (weights ride along) — the Lloyd sweeps still see every row.
    ``init_centers`` skips seeding entirely and starts Lloyd from the given
    (k, d) centers (how :func:`kmeans_multi` resumes its pruned winner).
    """
    n, d = x.shape
    w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
    backend = resolve_backend(assign_backend)
    if init_centers is not None:
        centers0 = init_centers
    else:
        centers0 = _seed_centers(key, x, k, w, seed_rows)
    # what the full-batch assignment reads: the kernel's slab, padded once
    # for every sweep, or x itself
    rows = prepare_rows(x, None, backend, chunk_size)

    def block_stats(xb, wb, centers, ab):
        idx, d2 = _assign_block(ab, centers, backend)
        oh = _labels_onehot(idx, k, wb, xb.dtype)
        sums = jnp.matmul(oh.T, xb, precision=MATMUL_PRECISION)
        return (jnp.sum(oh, axis=0), sums, jnp.sum(d2 * wb)), idx

    def sweep(centers):
        """One assignment pass -> ((counts, sums, inertia), assignments)."""
        if chunk_size is None:
            return block_stats(x, w, centers, rows)
        return streaming_map_reduce(
            lambda xb, wb: block_stats(xb, wb, centers, xb), (x, w),
            chunk_size)

    def update_block(xb, wb, centers, ab):
        """counts/sums only — the Lloyd loop never reads inertia, so the
        assignment reduces to ``argmax(x·c - ||c||²/2)``: one matmul per
        block, no per-row ``x²`` term or min-distance pass (both are
        assignment-invariant constants per row)."""
        if backend == "fused":
            idx, _ = _assign_block(ab, centers, backend)
        else:
            xc = jnp.matmul(xb, centers.T, precision=MATMUL_PRECISION)
            score = xc - 0.5 * jnp.sum(centers * centers, axis=1)[None, :]
            idx = jnp.argmax(score, axis=1).astype(jnp.int32)
        oh = _labels_onehot(idx, k, wb, xb.dtype)
        return (jnp.sum(oh, axis=0),
                jnp.matmul(oh.T, xb, precision=MATMUL_PRECISION))

    def sweep_stats(centers):
        """Reduce-only sweep for the Lloyd loop (assignments not collected)."""
        if chunk_size is None:
            return update_block(x, w, centers, rows)
        return streaming_reduce(
            lambda xb, wb: update_block(xb, wb, centers, xb), (x, w),
            chunk_size)

    def cond(state):
        _, it, shift = state
        return jnp.logical_and(it < max_iter, shift > tol)

    def body(state):
        centers, it, _ = state
        counts, sums = sweep_stats(centers)
        new_centers = jnp.where(
            counts[:, None] > 0,
            sums / jnp.maximum(counts[:, None], 1e-12), centers)
        shift = jnp.sum((new_centers - centers) ** 2)
        return new_centers, it + 1, shift

    state = (centers0, jnp.array(0), jnp.array(jnp.inf, x.dtype))
    centers, n_iter, _ = jax.lax.while_loop(cond, body, state)
    # Final sweep against the returned centers: the loop body scores the
    # pre-update centers, which used to skew kmeans_multi's restart pick.
    (counts, _, inertia), assign = sweep(centers)
    return KMeansResult(centers, assign, inertia, n_iter, counts)


@partial(jax.jit, static_argnames=("k", "max_iter", "n_init", "chunk_size",
                                   "assign_backend", "pilot_iters",
                                   "seed_rows"))
def kmeans_multi(key: jax.Array, x: jax.Array, k: int,
                 sample_weight: Optional[jax.Array] = None,
                 max_iter: int = 100, tol: float = 1e-4,
                 n_init: int = 4,
                 chunk_size: Optional[int] = None,
                 assign_backend: str = "auto",
                 pilot_iters: int = PILOT_ITERS,
                 seed_rows: int = SEED_ROWS) -> KMeansResult:
    """Best of ``n_init`` k-means restarts (lowest inertia) — sklearn-style
    robustness against bad seeding, which matters for small local client
    datasets.

    Restarts are **pilot-pruned**: every seed runs ``pilot_iters`` fixed
    Lloyd sweeps under one vmap, the seed with the lowest pilot inertia
    wins, and only the winner iterates to convergence. The previous
    vmap-of-while_loop design ran ALL restarts in lockstep until the
    slowest straggler converged — one bad seed spinning 38 iterations at
    n_init-wide cost was the committed 6.3s ``init_from_kmeans_chunked``
    outlier. Beyond ``seed_rows`` rows the pilot (and the winner's
    convergence run) operate on one shared uniform row subsample, with a
    bounded :data:`REFINE_ITERS` full-data polish at the end — so the
    full data is swept O(1) times, not O(iterations). The winner's
    returned stats are always recomputed against its final centers on the
    full data (see :func:`kmeans`), so restart selection quality is
    judged on real inertia downstream.
    """
    if n_init == 1:
        return kmeans(key, x, k, sample_weight, max_iter, tol, chunk_size,
                      assign_backend, seed_rows=seed_rows)
    n = x.shape[0]
    w = (jnp.ones(n, x.dtype) if sample_weight is None else sample_weight)
    backend = resolve_backend(assign_backend)
    # The pilot's only job is picking a seed, so beyond ``seed_rows`` rows
    # its sweeps run on one shared uniform subsample (weights ride along,
    # full-batch — the subsample working set is O(seed_rows·d) by
    # construction). Only the pruned winner ever sweeps the full data.
    if n > seed_rows:
        key, sub = jax.random.split(key)
        sidx = jax.random.randint(sub, (seed_rows,), 0, n)
        xs, ws, pilot_chunk = x[sidx], w[sidx], None
    else:
        xs, ws, pilot_chunk = x, w, chunk_size
    keys = jax.random.split(key, n_init)
    # one slab of the pilot's rows for every restart and sweep
    pilot_rows = prepare_rows(xs, None, backend, pilot_chunk)

    def sweep_stats(centers):
        if pilot_chunk is None:
            return _sweep_block(xs, ws, centers, backend, pilot_rows)
        return streaming_reduce(
            lambda xb, wb: _sweep_block(xb, wb, centers, backend),
            (xs, ws), pilot_chunk)

    def pilot(kk):
        centers = kmeans_plusplus(kk, xs, k, ws)

        def body(_, carry):
            centers, _ = carry
            counts, sums, inertia = sweep_stats(centers)
            new_centers = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts[:, None], 1e-12), centers)
            return new_centers, inertia

        return jax.lax.fori_loop(
            0, pilot_iters, body, (centers, jnp.array(jnp.inf, x.dtype)))

    pilot_centers, pilot_inertia = jax.vmap(pilot)(keys)
    best = jnp.argmin(pilot_inertia)
    if n > seed_rows:
        # Coreset-style finish: converge the winner on the subsample
        # (sweeps are ~n/seed_rows cheaper), then a bounded full-data
        # refine — the returned assignments/inertia/sizes all come from
        # the final full-data sweeps.
        sub = kmeans(key, xs, k, sample_weight=ws, max_iter=max_iter,
                     tol=tol, assign_backend=assign_backend,
                     init_centers=pilot_centers[best])
        res = kmeans(key, x, k, sample_weight, min(max_iter, REFINE_ITERS),
                     tol, chunk_size, assign_backend,
                     init_centers=sub.centers)
        return res._replace(n_iter=res.n_iter + sub.n_iter + pilot_iters)
    res = kmeans(key, x, k, sample_weight, max_iter, tol, chunk_size,
                 assign_backend, init_centers=pilot_centers[best])
    return res._replace(n_iter=res.n_iter + pilot_iters)


def kmeans_fit_cfg(key: jax.Array, x, k: int, config: FitConfig,
                   sample_weight: Optional[jax.Array] = None,
                   n_init: int = 1) -> KMeansResult:
    """The cfg-core k-means trainer behind ``repro.api.KMeansEstimator``:
    one validated :class:`FitConfig`, one dispatch — resident arrays run
    the jitted Lloyd loops (:func:`kmeans` / :func:`kmeans_multi`), a
    :class:`DataSource` runs the host-driven out-of-core twins. ``n_init``
    > 1 keeps the best restart by final-center inertia. ``tol`` and
    ``max_iter`` resolve through the "kmeans" algorithm defaults
    (1e-4 / 100), so a default config matches the legacy ``kmeans`` entry
    point without callers pinning the knobs."""
    backend = config.backend
    tol = config.resolve_tol("kmeans")
    max_iter = config.resolve_max_iter("kmeans")
    if isinstance(x, DataSource):
        require_array_weights(sample_weight, "k-means over a DataSource")
        cs = config.resolve_chunk(source=True)
        if n_init == 1:
            return kmeans_source(key, x, k, max_iter=max_iter,
                                 tol=tol, chunk_size=cs,
                                 assign_backend=backend)
        return kmeans_multi_source(key, x, k, max_iter=max_iter,
                                   tol=tol, n_init=n_init,
                                   chunk_size=cs, assign_backend=backend)
    cs = config.resolve_chunk(source=False)
    if n_init == 1:
        return kmeans(key, x, k, sample_weight=sample_weight,
                      max_iter=max_iter, tol=tol,
                      chunk_size=cs, assign_backend=backend)
    return kmeans_multi(key, x, k, sample_weight=sample_weight,
                        max_iter=max_iter, tol=tol,
                        n_init=n_init, chunk_size=cs, assign_backend=backend)


def federated_kmeans(key: jax.Array, client_data, k_global: int,
                     k_local: Optional[int] = None,
                     client_weights: Optional[jax.Array] = None,
                     max_iter: int = 100,
                     chunk_size: Optional[int] = None,
                     assign_backend: str = "auto") -> jax.Array:
    """One-shot federated k-means (Dennis et al. '21).

    Each client runs local k-means; the server clusters the (weighted) local
    centers to produce global centers. ``chunk_size``/``assign_backend``
    select the Lloyd-sweep engine for the client-side runs (the server-side
    run is over C·K_local centers — already tiny).

    client_data : (C, N_c, d) padded client datasets, or a list/tuple of
        per-client :class:`DataSource` streams (each client then runs its
        local k-means out-of-core; ragged sizes need no padding or masks)
    client_weights : (C, N_c) 0/1 mask (or general weights) for padding;
        array clients only (source rows all have weight 1)
    Returns (k_global, d) global centers.
    """
    if is_source_list(client_data):
        if client_weights is not None:
            raise ValueError(
                "federated_kmeans over DataSources: client_weights is "
                "array-path-only (weights mask padded fixed-shape client "
                "arrays; source shards are ragged by nature and every "
                "source row has weight 1)")
        return _federated_kmeans_sources(key, client_data, k_global,
                                         k_local=k_local, max_iter=max_iter,
                                         chunk_size=chunk_size,
                                         assign_backend=assign_backend)
    c = client_data.shape[0]
    k_local = k_local or k_global
    keys = jax.random.split(key, c + 1)

    def local(key, x, w):
        res = kmeans(key, x, k_local, sample_weight=w, max_iter=max_iter,
                     chunk_size=chunk_size, assign_backend=assign_backend)
        return res.centers, res.cluster_sizes

    if client_weights is None:
        client_weights = jnp.ones(client_data.shape[:2], client_data.dtype)
    centers, sizes = jax.vmap(local)(keys[:c], client_data, client_weights)  # (C,k,d),(C,k)
    flat_centers = centers.reshape(-1, client_data.shape[-1])
    flat_sizes = sizes.reshape(-1)
    res = kmeans(keys[-1], flat_centers, k_global,
                 sample_weight=flat_sizes, max_iter=max_iter)
    return res.centers


@partial(jax.jit, static_argnames=("mesh", "axis", "k_global", "k_local",
                                   "max_iter", "chunk_size",
                                   "assign_backend"))
def federated_kmeans_sharded(key: jax.Array, client_data: jax.Array,
                             client_weights: jax.Array, *, mesh,
                             k_global: int, axis: str = "data",
                             k_local: Optional[int] = None,
                             max_iter: int = 100,
                             chunk_size: Optional[int] = None,
                             assign_backend: str = "auto") -> jax.Array:
    """:func:`federated_kmeans` over clients sharded on the ``axis`` of
    ``mesh``, with its key schedule, so both give the same centers up to
    summation order. All of it runs inside ``shard_map``, where the
    kernels see one chip's arrays: each shard vmaps the local k-means
    over its own clients, one ``all_gather`` brings every client's
    (k_local, d) centers and cluster sizes to every shard, and each shard
    runs the server clustering on them alike. Returns (k_global, d)
    centers, replicated over the mesh."""
    c, _, d = client_data.shape
    k_local = k_local or k_global
    per_shard = c // mesh.shape[axis]

    def shard_fn(key, x_s, w_s):
        # every shard draws the whole schedule and keeps its own clients'
        # keys: slicing a sharded key array would be a collective of its own
        keys = jax.random.split(key, c + 1)
        keys_s = jax.lax.dynamic_slice_in_dim(
            keys, jax.lax.axis_index(axis) * per_shard, per_shard)

        def local(kk, x, w):
            res = kmeans(kk, x, k_local, sample_weight=w, max_iter=max_iter,
                         chunk_size=chunk_size,
                         assign_backend=assign_backend)
            return jnp.concatenate([res.centers, res.cluster_sizes[:, None]],
                                   axis=1)
        # === the init's one communication: (C, k_local, d + 1) ===
        local_all = jax.lax.all_gather(jax.vmap(local)(keys_s, x_s, w_s),
                                       axis, tiled=True)
        res = kmeans(keys[-1], local_all[..., :d].reshape(-1, d), k_global,
                     sample_weight=local_all[..., d].reshape(-1),
                     max_iter=max_iter)
        return res.centers

    spec = P(axis)
    return jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(), spec, spec),
                         out_specs=P(), check_vma=False)(
                             key, client_data, client_weights)


def gathered_floats(clients: int, k_local: int, d: int) -> int:
    """Floats a one-shot federated k-means collects: every client's
    ``k_local`` centers and cluster sizes (Dennis et al. '21)."""
    return clients * (k_local * d + k_local)


# ----------------------------------------------------------------------
# Out-of-core k-means: host-driven loops over DataSource blocks (§7)
# ----------------------------------------------------------------------
# Per-block functions are module-level jitted with parameters (centers,
# keys) as traced arguments, so every pass over a source hits the trace
# cache after the first block of each shape.

@jax.jit
def _seed_block(centers: jax.Array, valid: jax.Array, round_key: jax.Array,
                start: jax.Array, xb: jax.Array, wb: jax.Array):
    """One k-means++ sampling round over one block via the Gumbel-max
    trick: sampling a row with probability ∝ min-distance² equals taking
    the argmax of ``log(min_d²) + Gumbel``. Per-row Gumbel noise is keyed
    by the global row index, so the draw is chunking-invariant, and block
    maxima compose into the global argmax on the host — a streamed
    categorical sample without an (N,) probability vector. With no valid
    centers yet (round 0) the score degenerates to pure Gumbel noise,
    i.e. a uniform first-center draw. ``wb`` is the prefetch pad mask:
    padded rows score -inf, so they can never be drawn as a center."""
    b = xb.shape[0]
    idx = jnp.arange(b, dtype=jnp.uint32) + start
    row_keys = jax.vmap(jax.random.fold_in, (None, 0))(round_key, idx)
    g = jax.vmap(lambda kk: jax.random.gumbel(kk, (), xb.dtype))(row_keys)
    d2 = jnp.where(valid[None, :], _sq_dists(xb, centers), jnp.inf)
    d2min = jnp.min(d2, axis=1)
    base = jnp.where(jnp.isfinite(d2min),
                     jnp.log(jnp.maximum(d2min, 1e-30)), 0.0)
    score = jnp.where(wb > 0, base + g, -jnp.inf)
    i = jnp.argmax(score)
    return score[i], xb[i]


def kmeans_plusplus_streaming(key: jax.Array, source: DataSource, k: int,
                              chunk_size: Optional[int] = None) -> jax.Array:
    """k-means++ seeding over a :class:`DataSource` -> (k, d).

    The ROADMAP's last resident-array scan: each of the k rounds streams
    the blocks once (through the prefetching loader), recomputing min
    distances against the centers chosen so far (O(k²·N·d) total instead
    of the cached-min-d O(k·N·d) of the resident pass — the price of
    holding no (N,) state)."""
    chunk_size = resolve_source_chunk(chunk_size)
    d = source.dim
    centers = jnp.zeros((k, d), source.dtype)
    valid = jnp.zeros((k,), bool)
    for r in range(k):
        round_key = jax.random.fold_in(key, r)
        best_score, best_row = -float("inf"), None
        start = 0
        for xb, wb in prefetch_blocks(source, chunk_size):
            score, row = _seed_block(centers, valid, round_key,
                                     jnp.uint32(start), xb, wb)
            score = float(score)
            if score > best_score:
                best_score, best_row = score, row
            start += xb.shape[0]
        centers = centers.at[r].set(best_row)
        valid = valid.at[r].set(True)
    return centers


@partial(jax.jit, static_argnames=("backend",))
def _lloyd_block(centers: jax.Array, xb: jax.Array, wb: jax.Array,
                 backend: str):
    """(counts, sums, inertia) of one block — the Lloyd-sweep sufficient
    statistics the host loop accumulates. ``wb`` is the prefetch pad mask
    (source rows all carry weight 1; padded rows weight 0)."""
    return _sweep_block(xb, wb, centers, backend)


@partial(jax.jit, static_argnames=("covariance_type", "backend"))
def kmeans_label_block(centers: jax.Array, xb: jax.Array, wb: jax.Array,
                       covariance_type: str, backend: str) -> SufficientStats:
    """Hard-assignment label statistics of one block against fixed centers
    — the out-of-core replacement for ``label_stats``: assignment and
    labelling fuse into one pass, so the (N,) label vector of the resident
    init never exists. ``wb`` masks prefetch pad rows out of every sum."""
    k = centers.shape[0]
    idx, _ = _assign_block(xb, centers, backend)
    oh = _labels_onehot(idx, k, wb, xb.dtype)
    s0 = jnp.sum(oh, axis=0)
    s1 = jnp.matmul(oh.T, xb, precision=MATMUL_PRECISION)
    if covariance_type == "diag":
        s2 = jnp.matmul(oh.T, xb * xb, precision=MATMUL_PRECISION)
    else:
        s2 = jnp.einsum("nk,ni,nj->kij", oh, xb, xb,
                        precision=MATMUL_PRECISION)
    return SufficientStats(s0, s1, s2, jnp.zeros((), xb.dtype),
                           jnp.sum(wb))


def lloyd_round_stats(centers: jax.Array, x, sample_weight=None,
                      assign_backend: str = "reference",
                      chunk_size: Optional[int] = None):
    """One weighted Lloyd sweep against *fixed* centers ->
    ``(counts (K,), sums (K, d), inertia ())`` — the per-center label
    statistics one federated k-means client ships each round (Garst et
    al.; DESIGN.md §9). Additive in N, so per-client results sum into the
    server-side center update exactly like EM sufficient statistics.

    ``x`` is a resident ``(N, d)`` array (``sample_weight`` masks padded
    rows) or a :class:`DataSource` (never padded, no weights); either way
    the reduction runs through the §6 engine, so ``chunk_size`` bounds
    the working set. ``assign_backend`` must arrive resolved (the caller
    sits inside jit where "auto" has already been pinned)."""
    if isinstance(x, DataSource):
        require_array_weights(sample_weight,
                              "lloyd_round_stats over a DataSource")
        return reduce_rows(
            lambda xb, wb: _lloyd_block(centers, xb, wb, assign_backend), x,
            chunk_size)
    w = (jnp.ones(x.shape[0], x.dtype) if sample_weight is None
         else sample_weight)
    return reduce_rows(
        lambda xb, wb: _sweep_block(xb, wb, centers, assign_backend),
        (x, w), chunk_size)


def kmeans_source(key: jax.Array, source: DataSource, k: int,
                  max_iter: int = 100, tol: float = 1e-4,
                  chunk_size: Optional[int] = None,
                  assign_backend: str = "auto",
                  init_centers: Optional[jax.Array] = None) -> KMeansResult:
    """Lloyd's algorithm over a :class:`DataSource`: streamed k-means++
    seeding, then host-driven sweeps accumulating (counts, sums, inertia)
    per block. Mirrors :func:`kmeans` (same update, same stopping rule,
    final re-score against the returned centers) except that assignments
    are not collected — they would be the only O(N) output.
    ``init_centers`` skips seeding, as in :func:`kmeans`."""
    chunk_size = resolve_source_chunk(chunk_size)
    backend = resolve_backend(assign_backend)
    if init_centers is None:
        centers = kmeans_plusplus_streaming(key, source, k, chunk_size)
    else:
        centers = init_centers

    def sweep(c):
        return streaming_reduce(
            lambda xb, wb: _lloyd_block(c, xb, wb, backend),
            source, chunk_size)

    it, shift, tol = 0, float("inf"), float(tol)
    while it < max_iter and shift > tol:
        counts, sums, _ = sweep(centers)
        new_centers = jnp.where(
            counts[:, None] > 0,
            sums / jnp.maximum(counts[:, None], 1e-12), centers)
        shift = float(jnp.sum((new_centers - centers) ** 2))
        centers, it = new_centers, it + 1
    counts, _, inertia = sweep(centers)
    return KMeansResult(centers, None, inertia, jnp.asarray(it), counts)


def kmeans_multi_source(key: jax.Array, source: DataSource, k: int,
                        max_iter: int = 100, tol: float = 1e-4,
                        n_init: int = 4,
                        chunk_size: Optional[int] = None,
                        assign_backend: str = "auto",
                        pilot_iters: int = PILOT_ITERS) -> KMeansResult:
    """Best of ``n_init`` out-of-core restarts — the source twin of
    :func:`kmeans_multi`, pilot-pruned the same way: each seed streams
    ``pilot_iters`` fixed Lloyd sweeps, the lowest pilot inertia wins, and
    only the winner iterates to convergence (restarts run sequentially on
    the host; N full-convergence streams became one)."""
    if n_init == 1:
        return kmeans_source(key, source, k, max_iter=max_iter, tol=tol,
                             chunk_size=chunk_size,
                             assign_backend=assign_backend)
    chunk_size = resolve_source_chunk(chunk_size)
    backend = resolve_backend(assign_backend)
    best_centers, best_inertia = None, float("inf")
    for sub in jax.random.split(key, n_init):
        centers = kmeans_plusplus_streaming(sub, source, k, chunk_size)
        inertia = float("inf")
        for _ in range(pilot_iters):
            counts, sums, inertia = streaming_reduce(
                lambda xb, wb: _lloyd_block(centers, xb, wb, backend),
                source, chunk_size)
            centers = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts[:, None], 1e-12), centers)
            inertia = float(inertia)
        if inertia < best_inertia:
            best_centers, best_inertia = centers, inertia
    res = kmeans_source(key, source, k, max_iter=max_iter, tol=tol,
                        chunk_size=chunk_size, assign_backend=backend,
                        init_centers=best_centers)
    return res._replace(n_iter=res.n_iter + pilot_iters)


def federated_kmeans_from_sources(key: jax.Array,
                                  sources: Sequence[DataSource],
                                  k_global: int,
                                  k_local: Optional[int] = None,
                                  max_iter: int = 100,
                                  chunk_size: Optional[int] = None,
                                  assign_backend: str = "auto") -> jax.Array:
    """Deprecated: :func:`federated_kmeans` now dispatches on its input
    type, so a list of sources goes straight in. This shim forwards
    (bit-identical result) and will be removed."""
    warnings.warn(
        "federated_kmeans_from_sources is deprecated; pass the list of "
        "DataSources directly to federated_kmeans — same engine, same bits",
        DeprecationWarning, stacklevel=2)
    return federated_kmeans(key, list(sources), k_global, k_local=k_local,
                            max_iter=max_iter, chunk_size=chunk_size,
                            assign_backend=assign_backend)


def _federated_kmeans_sources(key: jax.Array,
                              sources: Sequence[DataSource],
                              k_global: int,
                              k_local: Optional[int] = None,
                              max_iter: int = 100,
                              chunk_size: Optional[int] = None,
                              assign_backend: str = "auto") -> jax.Array:
    """One-shot federated k-means with per-client :class:`DataSource` data:
    each client streams its own local k-means; the server clusters the
    size-weighted local centers (C·K_local rows — always resident-tiny).
    Ragged client sizes need no padding or masks on this path."""
    c = len(sources)
    k_local = k_local or k_global
    keys = jax.random.split(key, c + 1)
    centers, sizes = [], []
    for kk, src in zip(keys[:c], sources):
        res = kmeans_source(kk, src, k_local, max_iter=max_iter,
                            chunk_size=chunk_size,
                            assign_backend=assign_backend)
        centers.append(res.centers)
        sizes.append(res.cluster_sizes)
    flat_centers = jnp.concatenate(centers, axis=0)
    flat_sizes = jnp.concatenate(sizes, axis=0)
    res = kmeans(keys[-1], flat_centers, k_global,
                 sample_weight=flat_sizes, max_iter=max_iter)
    return res.centers
