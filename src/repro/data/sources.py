"""Out-of-core data sources: row streams the training engine can consume
without ever materializing the dataset (DESIGN.md §7).

A :class:`DataSource` is the host-side seam between storage (a file, a
generator, another process) and the device-side streaming-statistics engine
(``repro.core.em``): it knows its row count and feature dimension and can
iterate fixed-size `(chunk_size, dim)` blocks. Every statistic the training
pipeline reduces (``SufficientStats``, Lloyd-sweep stats, score sums) is
additive in N, so a host loop over blocks with a jitted per-block function
computes exactly the same numbers as the resident-array paths — with an
O(chunk · K) peak working set that is independent of N.

Block iteration is **restartable**: ``iter_blocks`` may be called any number
of times (EM takes one pass per iteration) and must yield the same rows in
the same order each time. Blocks are full ``chunk_size`` rows except the
final ragged remainder, and for a fixed dataset the row content must not
depend on ``chunk_size`` (only the block boundaries may) — that is what
makes fits reproducible across chunk sizes and bit-identical across source
types backed by the same rows.

Sources carry no sample weights: weights exist to make padded fixed-shape
federated arrays representable, and block streams are never padded. Ragged
client shards are expressed directly (:class:`ConcatSource`), so every row
a source yields has weight 1.

This module deliberately imports nothing from ``repro`` (it is below the
whole stack); :class:`SyntheticGMMSource` duck-types the ``GMM`` pytree
(``weights`` / ``means`` / ``covs`` attributes) instead of importing it.
"""
from __future__ import annotations

import abc
import os
import queue
import threading
from functools import partial
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def default_prefetch_depth() -> int:
    """Host-aware default lookahead for :func:`prefetch_blocks`.

    The producer thread only pays off when it has a core to run on: on a
    1–2-core host it competes with device compute and loses (the
    ``estep_source_prefetch{0,1,2}_us`` rows of BENCH_streaming.json
    document depth 0 winning there), so ``os.cpu_count() <= 2`` defaults
    to 0 (synchronous loop, no thread) and anything wider keeps the
    historical depth 2. The ``REPRO_PREFETCH_DEPTH`` environment
    variable overrides the heuristic outright (and call sites can always
    pass ``depth=`` explicitly).
    """
    env = os.environ.get("REPRO_PREFETCH_DEPTH")
    if env is not None:
        depth = int(env)
        if depth < 0:
            raise ValueError(
                f"REPRO_PREFETCH_DEPTH must be >= 0, got {env!r}")
        return depth
    cpus = os.cpu_count() or 1
    return 0 if cpus <= 2 else 2


# Default lookahead of :func:`prefetch_blocks` (how many prepared blocks a
# loader keeps in flight ahead of the consumer), auto-sized from the host
# core count. Module-level so tests and benchmarks can pin it (0 =
# synchronous loop, no thread).
PREFETCH_DEPTH = default_prefetch_depth()


def _check_chunk(chunk_size: int) -> int:
    chunk_size = int(chunk_size)
    if chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    return chunk_size


class DataSource(abc.ABC):
    """Protocol for out-of-core row streams: ``num_rows``, ``dim``,
    ``iter_blocks(chunk_size)`` (restartable, see module docstring)."""

    @property
    @abc.abstractmethod
    def num_rows(self) -> int:
        """Total number of rows the source yields per pass."""

    @property
    @abc.abstractmethod
    def dim(self) -> int:
        """Feature dimension of every yielded block."""

    @property
    def dtype(self):
        """Dtype of yielded blocks (canonicalized, i.e. what ``jnp`` will
        actually hand the engine)."""
        return jax.dtypes.canonicalize_dtype(jnp.float32)

    @abc.abstractmethod
    def iter_blocks(self, chunk_size: int) -> Iterator[jax.Array]:
        """Yield ``(b, dim)`` blocks with ``b == chunk_size`` everywhere but
        the final ragged block. Must be restartable and deterministic."""

    # ------------------------------------------------------------------
    def num_blocks(self, chunk_size: int) -> int:
        return -(-self.num_rows // _check_chunk(chunk_size))

    def materialize(self, chunk_size: int = 65536) -> jax.Array:
        """Concatenate all blocks into one resident ``(num_rows, dim)``
        array — O(N) memory by definition; for tests and small sources."""
        return jnp.concatenate(list(self.iter_blocks(chunk_size)), axis=0)

    def __len__(self) -> int:
        return self.num_rows

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(num_rows={self.num_rows}, "
                f"dim={self.dim}, dtype={jnp.dtype(self.dtype).name})")


# ----------------------------------------------------------------------
# Prefetching block loader (DESIGN.md §7): pad-and-mask + double buffering
# ----------------------------------------------------------------------

def pad_target(num_rows: int, chunk_size: int) -> int:
    """The ONE static row count every block of a ``(num_rows, chunk_size)``
    stream is padded to. Multi-block streams pad the ragged tail up to the
    full ``chunk_size`` (each per-block stage then compiles exactly once
    per chunk shape); single-block streams round up to a multiple of 64 so
    federated clients of slightly different sizes share traces instead of
    each forcing one."""
    chunk_size = _check_chunk(chunk_size)
    if num_rows > chunk_size:
        return chunk_size
    return min(chunk_size, -(-num_rows // 64) * 64)


@partial(jax.jit, static_argnames=("pad",))
def _pad_rows(xb: jax.Array, pad: int) -> jax.Array:
    return jnp.pad(xb, ((0, pad),) + ((0, 0),) * (xb.ndim - 1))


_MASK_CACHE: dict = {}


def _block_mask(target: int, valid: int, dtype) -> jax.Array:
    """(target,) 0/1 row mask with ``valid`` leading ones — cached, so
    every full block of a pass shares one buffer."""
    key = (target, valid, jnp.dtype(dtype).name)
    mask = _MASK_CACHE.get(key)
    if mask is None:
        mask = jnp.asarray(
            np.r_[np.ones(valid), np.zeros(target - valid)].astype(dtype))
        _MASK_CACHE[key] = mask
    return mask


_DONE = object()


def prefetch_blocks(source: DataSource, chunk_size: int,
                    depth: Optional[int] = None
                    ) -> Iterator[tuple[jax.Array, jax.Array]]:
    """Iterate ``(block, mask)`` pairs of a source with the next blocks
    prepared ahead of the consumer — the host-side loader every engine
    block loop drives (DESIGN.md §7).

    Two jobs, one seam:

    - **pad-and-mask**: every yielded block has the SAME static shape
      (:func:`pad_target` rows), with a cached 0/1 row mask marking real
      rows. Zero-padded rows carry weight 0 through every engine
      statistic, so per-block jitted stages compile once per chunk shape
      instead of once per distinct ragged tail.
    - **prefetch**: with ``depth > 0`` a background thread stays up to
      ``depth`` prepared blocks ahead, overlapping the host-side work of
      block i+1 (mmap page-in, synthetic generation dispatch, slicing,
      padding, ``jax.device_put``) with device compute on block i.
      ``depth`` defaults to the module-level :data:`PREFETCH_DEPTH`;
      ``depth=0`` runs the same prepare inline (no thread).

    Block order is never changed — the consumer sees exactly the
    partition ``iter_blocks`` emits, so accumulation order (and therefore
    the bit-identity of source-backed fits) is untouched.
    """
    chunk_size = _check_chunk(chunk_size)
    if depth is None:
        depth = PREFETCH_DEPTH
    target = pad_target(source.num_rows, chunk_size)
    dtype = source.dtype

    def prepare(xb):
        b = xb.shape[0]
        if b == target:
            return jax.device_put(xb), _block_mask(target, b, dtype)
        return (_pad_rows(jax.device_put(xb), target - b),
                _block_mask(target, b, dtype))

    if depth <= 0:
        for xb in source.iter_blocks(chunk_size):
            yield prepare(xb)
        return

    q: queue.Queue = queue.Queue(maxsize=int(depth))
    stop = threading.Event()

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for xb in source.iter_blocks(chunk_size):
                if not put((None, prepare(xb))):
                    return
            put((_DONE, None))
        except BaseException as exc:  # noqa: BLE001 — re-raised downstream
            put((exc, None))

    thread = threading.Thread(target=producer, daemon=True,
                              name="prefetch_blocks")
    thread.start()
    try:
        while True:
            tag, item = q.get()
            if tag is _DONE:
                return
            if tag is not None:
                raise tag
            yield item
    finally:
        stop.set()


class ArraySource(DataSource):
    """A resident array viewed as a source — the bridge that lets one code
    path serve both worlds, and the parity oracle for every other source."""

    def __init__(self, x):
        if x.ndim != 2:
            raise ValueError(f"ArraySource expects (N, d) rows, got {x.shape}")
        if x.shape[0] == 0:
            raise ValueError("ArraySource needs at least one row")
        self._x = x

    @property
    def num_rows(self) -> int:
        return int(self._x.shape[0])

    @property
    def dim(self) -> int:
        return int(self._x.shape[1])

    @property
    def dtype(self):
        return jax.dtypes.canonicalize_dtype(self._x.dtype)

    def iter_blocks(self, chunk_size: int) -> Iterator[jax.Array]:
        chunk_size = _check_chunk(chunk_size)
        for start in range(0, self.num_rows, chunk_size):
            yield jnp.asarray(self._x[start:start + chunk_size])


class NpyFileSource(DataSource):
    """Memory-mapped ``.npy`` rows: only the active block is ever copied
    into (device) memory; the OS page cache owns the rest."""

    def __init__(self, path):
        self._path = str(path)
        self._mm = np.load(self._path, mmap_mode="r")
        if self._mm.ndim != 2:
            raise ValueError(
                f"NpyFileSource expects a 2-D (N, d) array file, "
                f"got shape {self._mm.shape} in {self._path}")
        if self._mm.shape[0] == 0:
            raise ValueError(f"empty .npy source: {self._path}")

    @property
    def num_rows(self) -> int:
        return int(self._mm.shape[0])

    @property
    def dim(self) -> int:
        return int(self._mm.shape[1])

    @property
    def dtype(self):
        return jax.dtypes.canonicalize_dtype(self._mm.dtype)

    def iter_blocks(self, chunk_size: int) -> Iterator[jax.Array]:
        chunk_size = _check_chunk(chunk_size)
        for start in range(0, self.num_rows, chunk_size):
            # np.asarray slices exactly one block out of the mmap; the
            # device transfer is the only copy.
            yield jnp.asarray(np.asarray(self._mm[start:start + chunk_size]))


class ConcatSource(DataSource):
    """Row-wise concatenation of sources (ragged federated shards).

    Blocks are re-chunked across child boundaries, so the emitted block
    partition — and therefore every engine reduction, bit for bit — is
    identical to an :class:`ArraySource` over the concatenated rows, no
    matter how unevenly the children split them.
    """

    def __init__(self, sources: Sequence[DataSource]):
        sources = list(sources)
        if not sources:
            raise ValueError("ConcatSource needs at least one child source")
        dims = {s.dim for s in sources}
        if len(dims) != 1:
            raise ValueError(f"child sources disagree on dim: {sorted(dims)}")
        dtypes = {jnp.dtype(s.dtype) for s in sources}
        if len(dtypes) != 1:
            # Mixed dtypes would make a block's dtype depend on which
            # children it straddles — i.e. on the chunk partition — and
            # silently break the bit-parity contract above.
            raise ValueError("child sources disagree on dtype: "
                             f"{sorted(d.name for d in dtypes)}")
        self._sources = sources
        self._num_rows = sum(s.num_rows for s in sources)
        self._dim = sources[0].dim

    @property
    def num_rows(self) -> int:
        return self._num_rows

    @property
    def dim(self) -> int:
        return self._dim

    @property
    def dtype(self):
        return self._sources[0].dtype

    def iter_blocks(self, chunk_size: int) -> Iterator[jax.Array]:
        chunk_size = _check_chunk(chunk_size)
        pending: list[jax.Array] = []
        have = 0
        for src in self._sources:
            for block in src.iter_blocks(chunk_size):
                pending.append(block)
                have += block.shape[0]
                while have >= chunk_size:
                    buf = (pending[0] if len(pending) == 1
                           else jnp.concatenate(pending, axis=0))
                    yield buf[:chunk_size]
                    rest = buf[chunk_size:]
                    pending = [rest] if rest.shape[0] else []
                    have = rest.shape[0]
        if have:
            yield (pending[0] if len(pending) == 1
                   else jnp.concatenate(pending, axis=0))


# Generation granule of the mixture stream: draws are batched per TILE
# rows, with tiles aligned to GLOBAL row index (tile t owns rows
# [t*TILE, (t+1)*TILE)) — never to block position, so the stream stays
# invariant to ``chunk_size`` and restartable even though a block
# boundary can land mid-tile. Per tile there is ONE fold_in and two
# batched draws over all TILE rows: one uniform per row inverted through
# the mixture CDF (searchsorted) for the component, one (TILE, d) normal
# for the offset. The per-row spelling (fold_in + split + K-way gumbel
# categorical + normal per row) made generation ~3x the whole E-step on
# CPU (the estep_synthetic_source outlier in BENCH_streaming.json, now
# guarded by ``synthetic_vs_array``).
_TILE = 1024


@partial(jax.jit, static_argnames=("size",))
def _synth_block(cum_weights, means, scale, key, start, size):
    """Rows [start, start+size) of the mixture stream: generate the
    covering index-aligned tiles in one batched draw each, slice the
    block out. Worst-case waste is one tile of rows per block (a block
    never spans more than ``size // TILE + 2`` tiles)."""
    d = means.shape[1]
    ntiles = (size - 1) // _TILE + 2        # covers any tile alignment
    tile0 = start // _TILE
    tile_ids = tile0 + jnp.arange(ntiles, dtype=jnp.uint32)
    tile_keys = jax.vmap(jax.random.fold_in, (None, 0))(key, tile_ids)
    pair = jax.vmap(jax.random.split)(tile_keys)           # (ntiles, 2)
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (_TILE,)))(pair[:, 0])
    # u < 1 <= cum_weights[-1], so the right-bisection index is in [0, K)
    # and P(comp = j) is exactly the j-th mixture weight
    comp = jnp.searchsorted(cum_weights, u.reshape(-1), side="right")
    eps = jax.vmap(lambda kk: jax.random.normal(
        kk, (_TILE, d), means.dtype))(pair[:, 1]).reshape(-1, d)
    mu = means[comp]
    if scale.ndim == 2:                                     # diagonal: std
        rows = mu + scale[comp] * eps
    else:
        rows = mu + jnp.einsum("nij,nj->ni", scale[comp], eps,  # Cholesky
                               precision=jax.lax.Precision.HIGHEST)
    return jax.lax.dynamic_slice_in_dim(rows, start - tile0 * _TILE, size)


class SyntheticGMMSource(DataSource):
    """Samples from a GMM generated block-by-block from a seeded key — the
    server-side synthetic-replay set of FedGenGMM (|S| = H · Σ K_c) without
    materializing it up front. Re-iteration yields identical rows (from
    the bounded block cache when the source fits the ``cache_rows``
    budget, regenerated from the same keys otherwise), so a multi-pass
    EM fit sees one fixed virtual dataset either way.

    ``gmm`` is any object with ``weights (K,)``, ``means (K, d)`` and
    ``covs`` (``(K, d)`` diagonal variances or ``(K, d, d)`` full)
    attributes — i.e. a ``repro.core.gmm.GMM``, duck-typed to keep this
    module import-free below the stack.
    """

    def __init__(self, gmm, num_rows: int, key, cache_rows: int = 1 << 17):
        num_rows = int(num_rows)
        if num_rows <= 0:
            raise ValueError(f"num_rows must be positive, got {num_rows}")
        means = jnp.asarray(gmm.means)
        covs = jnp.asarray(gmm.covs)
        weights = jnp.asarray(gmm.weights)
        self._cum_weights = jnp.cumsum(weights / jnp.sum(weights))
        self._means = means
        self._scale = (jnp.sqrt(covs) if covs.ndim == 2
                       else jnp.linalg.cholesky(covs))
        self._key = key
        self._num_rows = num_rows
        # Generation costs real device time on EVERY pass of a multi-pass
        # fit (EM takes one pass per iteration) while the rows never
        # change. Sources within the `cache_rows` budget keep their
        # generated blocks after the first pass — a bounded memoization
        # (default 2^17 rows ≈ a few MB; the FedGen synthetic-replay sets
        # are a few thousand rows). Anything larger streams every pass,
        # so the O(chunk) working-set guarantee for big-N sources is
        # untouched (pinned by the million-row test in
        # tests/test_source_parity.py). ``cache_rows=0`` disables caching.
        self._cache_rows = int(cache_rows)
        self._cache: dict[int, list] = {}

    @property
    def num_rows(self) -> int:
        return self._num_rows

    @property
    def dim(self) -> int:
        return int(self._means.shape[1])

    @property
    def dtype(self):
        return self._means.dtype

    def iter_blocks(self, chunk_size: int) -> Iterator[jax.Array]:
        chunk_size = _check_chunk(chunk_size)
        if self._num_rows <= self._cache_rows:
            blocks = self._cache.get(chunk_size)
            if blocks is None:
                blocks = [self._gen_block(start, chunk_size)
                          for start in range(0, self._num_rows, chunk_size)]
                self._cache[chunk_size] = blocks
            yield from blocks
            return
        for start in range(0, self._num_rows, chunk_size):
            yield self._gen_block(start, chunk_size)

    def _gen_block(self, start: int, chunk_size: int) -> jax.Array:
        size = min(chunk_size, self._num_rows - start)
        return _synth_block(self._cum_weights, self._means, self._scale,
                            self._key, jnp.uint32(start), size)


class ShuffledSource(DataSource):
    """Windowed multi-epoch reshuffle of another source.

    ``epoch=0`` is an exact passthrough — same blocks, same order, bit for
    bit — so wrapping a source costs nothing until the caller actually asks
    for a new ordering. For ``epoch >= 1``, rows are permuted inside
    windows of ``window_blocks`` consecutive blocks (an O(window · chunk)
    buffer, never O(N)), with the permutation keyed by
    ``fold_in(fold_in(key, epoch), window_index)``: deterministic,
    restartable, and different every epoch. ``with_epoch(e)`` derives the
    next epoch's view without touching the wrapped source.

    Streamed fits are pass-order-pinned by the bit-identity contract;
    this wrapper is the sanctioned way to vary that order across epochs
    (e.g. minibatch-flavoured EM) without giving up determinism.
    """

    def __init__(self, inner: DataSource, key, epoch: int = 0,
                 window_blocks: int = 8):
        self._inner = inner
        self._key = key
        self._epoch = int(epoch)
        if self._epoch < 0:
            raise ValueError(f"epoch must be >= 0, got {epoch}")
        self._window_blocks = int(window_blocks)
        if self._window_blocks <= 0:
            raise ValueError(
                f"window_blocks must be positive, got {window_blocks}")

    @property
    def num_rows(self) -> int:
        return self._inner.num_rows

    @property
    def dim(self) -> int:
        return self._inner.dim

    @property
    def dtype(self):
        return self._inner.dtype

    @property
    def epoch(self) -> int:
        return self._epoch

    def with_epoch(self, epoch: int) -> "ShuffledSource":
        return ShuffledSource(self._inner, self._key, epoch,
                              self._window_blocks)

    def iter_blocks(self, chunk_size: int) -> Iterator[jax.Array]:
        chunk_size = _check_chunk(chunk_size)
        if self._epoch == 0:
            yield from self._inner.iter_blocks(chunk_size)
            return
        ekey = jax.random.fold_in(self._key, jnp.uint32(self._epoch))
        window: list[jax.Array] = []
        widx = 0

        def flush(window, widx):
            buf = (window[0] if len(window) == 1
                   else jnp.concatenate(window, axis=0))
            perm = jax.random.permutation(
                jax.random.fold_in(ekey, jnp.uint32(widx)), buf.shape[0])
            buf = buf[perm]
            for s in range(0, buf.shape[0], chunk_size):
                yield buf[s:s + chunk_size]

        for block in self._inner.iter_blocks(chunk_size):
            window.append(block)
            if len(window) == self._window_blocks:
                yield from flush(window, widx)
                window, widx = [], widx + 1
        if window:
            yield from flush(window, widx)


def as_source(x) -> DataSource:
    """Coerce an `(N, d)` array to :class:`ArraySource`; pass sources
    through unchanged."""
    if isinstance(x, DataSource):
        return x
    return ArraySource(x)
