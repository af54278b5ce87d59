"""Distributed EM (DEM) baselines (§5.4 of the paper, after Wu et al. '23).

Every client runs the E-step locally and ships sufficient statistics; the
server aggregates (a psum in the sharded runtime), runs the M-step, and
broadcasts the new parameters. One EM iteration = one communication round
— which makes DEM a one-screen :class:`DEMStrategy` on the federation
runtime (``repro.fed.runtime``, DESIGN.md §9): ``local_step`` is the
engine E-step, ``server_combine`` is the M-step plus the avg-loglik
convergence scalar, and :func:`run_rounds` owns the client loop, the
round loop and the communication ledger for every input type
(ClientSplit, list of DataSources, sharded mesh).

Three initializations of the global component centers are reproduced,
named in :class:`repro.core.config.FitConfig` init-strategy terms:
  "separated"  (init 1) — maximally separated centers in the (normalized)
               feature range,
  "pilot"      (init 2) — pilot GMM on a small (100-point) subset uploaded
               to the server,
  "fed-kmeans" (init 3) — one-shot federated k-means (Dennis et al. '21).

:func:`dem_cfg` dispatches on the client input type with one validated
:class:`FitConfig` and is what ``repro.api.DEM`` runs; its results are
bit-identical to the pre-runtime round loops (pinned in
``tests/test_fed_runtime.py``). The iterative FedEM baseline
(``repro.fed.strategies``) generalizes :class:`DEMStrategy` with
partial-participation / local-epochs knobs.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.config import (FitConfig, is_source_list, resolve_backend,
                               resolve_estep_backend)
from repro.core.em import (computed_lanes, e_step_stats, fit_gmm,
                           init_from_means, init_from_means_sharded, m_step,
                           prepare_rows, prepared_bytes, slab_row_block)
from repro.core.gmm import GMM
from repro.core.kmeans import (federated_kmeans, federated_kmeans_sharded,
                               gathered_floats)
from repro.core.partition import ClientSplit
from repro.data.sources import ConcatSource, DataSource
from repro.fed.ledger import (CommStats, dtype_itemsize, gmm_payload_floats,
                              RoundPayload, stats_payload_floats)
from repro.fed.runtime import run_rounds


class DEMResult(NamedTuple):
    global_gmm: GMM
    log_likelihood: jax.Array   # avg loglik over all client data
    n_rounds: jax.Array
    converged: jax.Array
    comm: CommStats


# DEM init schemes: paper numbering <-> FitConfig init-strategy names.
INIT_SCHEME_NAMES = {1: "separated", 2: "pilot", 3: "fed-kmeans"}
INIT_SCHEMES = {v: k for k, v in INIT_SCHEME_NAMES.items()}


def _legacy_init_name(init) -> str:
    """The one legacy-knob rule: paper scheme numbers (1/2/3) and
    FitConfig strategy names are both accepted, anything else is the
    historical error."""
    name = INIT_SCHEME_NAMES.get(init, init)
    if name not in INIT_SCHEMES:
        raise ValueError(f"unknown DEM init scheme {init}")
    return name


def _resolve_init(init: str, sources: bool) -> str:
    """``auto`` keeps the historical per-input defaults: fed-kmeans
    (init 3) for resident splits, separated centers (init 1) for source
    clients (the pilot subset would upload raw rows)."""
    if init == "auto":
        return "separated" if sources else "fed-kmeans"
    if init == "kmeans":
        raise ValueError(
            "init='kmeans' is the single-model GMM init; DEM init "
            "strategies are 'separated' | 'pilot' | 'fed-kmeans' (paper "
            "schemes 1/2/3) or 'auto'")
    return init


# ----------------------------------------------------------------------
# Initializations
# ----------------------------------------------------------------------

def max_separated_centers(key: jax.Array, k: int, d: int,
                          n_candidates: int = 2048) -> jax.Array:
    """Init 1: greedy farthest-point centers in the unit hypercube [0,1]^d
    (features are normalized to [0,1], §5.1)."""
    cand = jax.random.uniform(key, (n_candidates, d))
    center0 = jnp.full((d,), 0.5, cand.dtype)
    centers = jnp.zeros((k, d), cand.dtype).at[0].set(center0)
    min_d = jnp.sum((cand - center0) ** 2, axis=1)

    def body(i, carry):
        centers, min_d = carry
        idx = jnp.argmax(min_d)
        c = cand[idx]
        centers = centers.at[i].set(c)
        min_d = jnp.minimum(min_d, jnp.sum((cand - c) ** 2, axis=1))
        return centers, min_d

    centers, _ = jax.lax.fori_loop(1, k, body, (centers, min_d))
    return centers


# Init 2's pilot subset size (raw rows uploaded to the server) — also
# what the comm ledger charges a pilot init for.
PILOT_ROWS = 100


def pilot_subset_centers(key: jax.Array, split: ClientSplit, k: int,
                         n_pilot: int = PILOT_ROWS) -> jax.Array:
    """Init 2: clients upload a tiny uniform subset (n_pilot points total);
    the server fits a pilot GMM and uses its means. NOTE: uploads raw data."""
    data = jnp.asarray(split.data).reshape(-1, split.data.shape[-1])
    mask = jnp.asarray(split.mask).reshape(-1)
    # weighted sampling without replacement over real (unpadded) rows
    g = jax.random.gumbel(key, mask.shape)
    scores = jnp.where(mask > 0, g, -jnp.inf)
    idx = jax.lax.top_k(scores, n_pilot)[1]
    pilot = data[idx]
    res = fit_gmm(jax.random.fold_in(key, 1), pilot, k, max_iter=100)
    return res.gmm.means


def fed_kmeans_centers(key: jax.Array, split: ClientSplit, k: int,
                       chunk_size: int | None = None) -> jax.Array:
    """Init 3: one-shot federated k-means global centers. ``chunk_size``
    streams the client-side Lloyd sweeps (DESIGN.md §6)."""
    return federated_kmeans(key, jnp.asarray(split.data), k,
                            client_weights=jnp.asarray(split.mask),
                            chunk_size=chunk_size)


def init_sharded(key: jax.Array, backend, k: int, init: str, *,
                 covariance_type: str = "diag", reg_covar: float = 1e-6,
                 chunk: Optional[int] = None) -> GMM:
    """The round-0 model of DEM-style strategies on sharded clients
    (``repro.fed.runtime.ShardedClients``), where every kernel has to run
    inside ``shard_map``: init 3's centers from
    :func:`~repro.core.kmeans.federated_kmeans_sharded` (the
    single-process key schedule: ``key`` is what
    :func:`federated_kmeans` would get), or init 1's, then the data
    moments by :func:`~repro.core.em.init_from_means_sharded`."""
    if init == "fed-kmeans":
        centers = federated_kmeans_sharded(
            key, backend.data, backend.mask, mesh=backend.mesh, k_global=k,
            axis=backend.axis, chunk_size=chunk)
    elif init == "separated":
        centers = max_separated_centers(key, k, backend.dim)
    else:
        raise ValueError(
            f"DEM init {init!r} on sharded clients: use 'fed-kmeans' or "
            f"'separated' ('pilot' uploads raw rows to one server)")
    return init_from_means_sharded(
        centers, backend.data, backend.mask, mesh=backend.mesh,
        axis=backend.axis, covariance_type=covariance_type,
        reg_covar=reg_covar)


# ----------------------------------------------------------------------
# DEM as a federation strategy
# ----------------------------------------------------------------------

class DEMState(NamedTuple):
    """Round-loop state: the global model plus the convergence scalars.
    Leaves are jnp under the jitted driver and Python floats on the host
    (source-client) path, mirroring the engine's ``host_em_loop``
    semantics; tol/reg_covar ride here as *traced* values so sweeping
    them never recompiles the loop."""
    gmm: GMM
    prev_ll: jax.Array
    ll: jax.Array
    tol: jax.Array
    reg_covar: jax.Array


@dataclasses.dataclass(frozen=True)
class DEMStrategy:
    """Distributed EM on the federation runtime: clients ship
    :class:`~repro.core.em.SufficientStats`, the server M-steps, one EM
    iteration per communication round. Frozen/hashable so it rides the
    jitted round driver as a static argument; ``tol``/``reg_covar`` are
    ``compare=False`` because they enter the computation through the
    (traced) state, never the cache key."""

    k: int
    covariance_type: str = "diag"
    backend: str = "auto"            # engine knob (resolved per op)
    chunk: Optional[int] = None      # resolved for the input type
    init: str = "fed-kmeans"
    host: bool = False               # source clients -> host round loop
    tol: float = dataclasses.field(default=1e-3, compare=False)
    reg_covar: float = dataclasses.field(default=1e-6, compare=False)

    one_shot = False
    name = "dem"

    # -- init ----------------------------------------------------------

    def init_state(self, key: jax.Array, backend) -> DEMState:
        k_init, _ = jax.random.split(key)
        if backend.kind == "sources":
            d = backend.dim
            if self.init == "separated":
                centers = max_separated_centers(k_init, self.k, d)
            elif self.init == "fed-kmeans":
                centers = federated_kmeans(k_init, list(backend.sources),
                                           self.k, chunk_size=self.chunk)
            else:  # "pilot"
                raise ValueError(
                    "DEM init 'pilot' uploads raw rows and needs resident "
                    "client data; use a ClientSplit for it")
            union = ConcatSource(backend.sources)
            gmm0 = init_from_means(centers, union,
                                   covariance_type=self.covariance_type,
                                   reg_covar=self.reg_covar,
                                   chunk_size=self.chunk)
            return self.state_from_gmm(gmm0)
        data, mask = backend.data, backend.mask
        d = data.shape[-1]
        if backend.kind == "sharded":
            return self.state_from_gmm(
                init_sharded(k_init, backend, self.k, self.init,
                             covariance_type=self.covariance_type,
                             reg_covar=self.reg_covar, chunk=self.chunk),
                dtype=data.dtype)
        if self.init == "separated":
            centers = max_separated_centers(k_init, self.k, d)
        elif self.init == "pilot":
            split = getattr(backend, "split", None)
            if split is None:
                raise ValueError(
                    "DEM init 'pilot' needs a ClientSplit (it uploads a "
                    "raw pilot subset)")
            centers = pilot_subset_centers(k_init, split, self.k)
        else:  # "fed-kmeans" (validated upstream)
            centers = federated_kmeans(k_init, data, self.k,
                                       client_weights=mask,
                                       chunk_size=self.chunk)
        flat = data.reshape(-1, d)
        flat_w = mask.reshape(-1)
        gmm0 = init_from_means(centers, flat, flat_w,
                               covariance_type=self.covariance_type,
                               reg_covar=self.reg_covar)
        return self.state_from_gmm(gmm0, dtype=data.dtype)

    def state_from_gmm(self, gmm0: GMM, dtype=None) -> "DEMState":
        """Round-0 state around an externally built initial model — what
        ``init_state`` ends in, and what the sharded entry point uses to
        honor caller-chosen init centers. ``dtype`` (the data dtype) pins
        the convergence scalars on the jitted path; the host (source)
        path carries Python floats instead."""
        if self.host:
            neg_inf = float("-inf")
            return self._make_state(gmm0, neg_inf, neg_inf,
                                    float(self.tol), float(self.reg_covar))
        neg_inf = jnp.array(-jnp.inf, dtype)
        return self._make_state(gmm0, neg_inf, neg_inf,
                                jnp.asarray(self.tol, dtype), self.reg_covar)

    def _make_state(self, gmm, prev_ll, ll, tol, reg_covar):
        return DEMState(gmm, prev_ll, ll, tol, reg_covar)

    # -- one round ------------------------------------------------------

    def local_step(self, state: DEMState, x, w, idx):
        """One client's E-step over its own rows (or their slab,
        :meth:`prepare_client`) -> SufficientStats (the uplink payload;
        additive, so backends sum it)."""
        return e_step_stats(state.gmm, x, w, self.backend, self.chunk)

    def _estep(self) -> str:
        return resolve_estep_backend(self.backend,
                                     self.covariance_type == "diag")

    def lanes_computed(self, d: int) -> int:
        """Feature width a client's E-step computes over."""
        return computed_lanes(d, self._estep())

    def prepare_client(self, x, w):
        """One client's rows as its E-step reads them in every round: the
        fused kernel's slab, padded once before the round loop, or the
        rows themselves (``repro.core.em.prepare_rows``)."""
        return prepare_rows(x, w, self._estep(), self.chunk)

    def row_block(self):
        """Rows of the blocks a client's E-step computes up to its last
        weighted row, where its slab lets it skip the rest; else None."""
        return slab_row_block(self._estep(), self.chunk)

    def prepared_bytes(self, backend, phase: str):
        """Device bytes of the clients' rows padded once for the kernels:
        in the ``"loop"`` phase the E-step's slabs, in the ``"init"``
        phase the fed-kmeans init's Lloyd loops' (resident clients; on
        sharded clients, one chip's). None where the kernels get raw
        arrays."""
        if backend.host:
            return None
        n, d = backend.data.shape[1], backend.dim
        c = backend.clients_per_shard if backend.kind == "sharded" \
            else backend.num_clients
        if phase == "loop":
            each = prepared_bytes(n, d, self._estep(), self.chunk)
        elif phase == "init" and self.init == "fed-kmeans":
            # federated_kmeans assigns on the "auto" backend
            each = prepared_bytes(n, d, resolve_backend("auto"), self.chunk,
                                  weights=False)
        else:
            each = None
        return None if each is None else c * each

    def gathered_bytes(self, backend):
        """Bytes the fed-kmeans init's ``all_gather`` brings to each chip of
        sharded clients: every client's k centers and cluster sizes."""
        if backend.kind != "sharded" or self.init != "fed-kmeans":
            return None
        return gathered_floats(backend.num_clients, self.k, backend.dim) \
            * dtype_itemsize(backend.data.dtype)

    def server_combine(self, state: DEMState, stats) -> DEMState:
        gmm = m_step(stats, state.reg_covar)
        ll = stats.loglik / jnp.maximum(stats.wsum, 1e-12)
        if self.host:
            ll = float(ll)
        return self._next_state(state, gmm, ll)

    def _next_state(self, state, gmm, ll):
        return DEMState(gmm, state.ll, ll, state.tol, state.reg_covar)

    def converged(self, state: DEMState):
        return abs(state.ll - state.prev_ll) <= state.tol

    def keep_going(self, state: DEMState):
        """The historical loop predicate, kept distinct from
        ``converged``: with a NaN loglik (degenerate run) both are false,
        so the loop stops after one more round AND reports not-converged
        — exactly the pre-§9 ``_dem_loop`` / ``host_em_loop`` behavior."""
        return abs(state.ll - state.prev_ll) > state.tol

    # -- accounting / result -------------------------------------------

    def round_payload(self, backend, state) -> RoundPayload:
        c, d = backend.num_clients, backend.dim
        diag = self.covariance_type == "diag"
        # Under a cohort sampler the driver's accounting view reports
        # num_clients == cohort size (per-round traffic) while
        # population_clients stays C — init-phase traffic touches the
        # whole population exactly once.
        pop = getattr(backend, "population_clients", c)
        if self.init == "fed-kmeans":
            # one-shot warm start: every client uploads its k local
            # centers + k cluster sizes (Dennis et al. '21)
            init_up = gathered_floats(pop, self.k, d)
        elif self.init == "pilot":
            init_up = PILOT_ROWS * d   # raw pilot rows to the server
        else:  # "separated": server-side construction, no uplink
            init_up = 0
        return RoundPayload(
            uplink_floats=c * stats_payload_floats(self.k, d, diag),
            downlink_floats=c * gmm_payload_floats(self.k, d, diag),
            itemsize=dtype_itemsize(state.gmm.means.dtype),
            extra_uplink_floats=init_up,
            # the round-0 global model broadcast (every init scheme ends
            # in one; warm starts used to ride the ledger for free)
            extra_downlink_floats=pop * gmm_payload_floats(self.k, d, diag))

    def finalize(self, state: DEMState, n_rounds, converged,
                 comm: CommStats) -> DEMResult:
        ll = state.ll
        if self.host:
            ll = jnp.asarray(ll, state.gmm.means.dtype)
        return DEMResult(state.gmm, ll, n_rounds, jnp.asarray(converged),
                         comm)


def dem_cfg(key: jax.Array, clients, config: FitConfig, k: int,
            transform=None, async_policy=None, mesh=None) -> DEMResult:
    """Run DEM — the cfg-core behind ``repro.api.DEM``, dispatching on the
    client input type (:class:`ClientSplit` vs list of
    :class:`DataSource`) through the federation runtime. The init strategy
    comes from ``config.init`` ("auto" resolves to fed-kmeans for splits,
    separated centers for sources; "pilot" requires resident data — it
    uploads raw rows). ``async_policy`` (a
    :class:`repro.fed.AsyncPolicy`) reroutes the rounds through the
    buffered asynchronous driver (``repro.fed.run_async``, DESIGN.md
    §12); None keeps the synchronous loop. A ``mesh`` shards a split's
    clients over its ``"data"`` axis (``ShardedClients``, DESIGN.md §9):
    one ``psum`` of the statistics per round."""
    sources = is_source_list(clients)
    if not sources and not isinstance(clients, ClientSplit):
        raise TypeError(
            f"dem clients must be a ClientSplit or a list of DataSources, "
            f"got {type(clients).__name__}")
    strategy = DEMStrategy(
        k=k, covariance_type=config.covariance_type, backend=config.backend,
        chunk=config.resolve_chunk(source=sources),
        init=_resolve_init(config.init, sources), host=sources,
        tol=config.resolve_tol("em"), reg_covar=config.reg_covar)
    if async_policy is not None:
        from repro.fed.async_runtime import run_async  # sits beside runtime
        return run_async(strategy, clients, key=key,
                         max_rounds=config.resolve_max_iter("em"),
                         transform=transform, **async_policy.driver_kwargs())
    return run_rounds(strategy, clients, key=key,
                      max_rounds=config.resolve_max_iter("em"),
                      transform=transform, mesh=mesh)


def dem(key: jax.Array, split: ClientSplit, k: int, init: int = 3,
        max_rounds: int = 200, tol: float = 1e-3,
        reg_covar: float = 1e-6, estep_backend: str = "auto",
        chunk_size: int | None = None,
        covariance_type: str = "diag") -> DEMResult:
    """Legacy keyword surface of :func:`dem_cfg` (internal; prefer
    ``repro.api.DEM``). ``init`` takes the paper's scheme numbers 1/2/3
    (or their FitConfig names)."""
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_rounds, init=_legacy_init_name(init))
    return dem_cfg(key, split, cfg, k)


def dem_from_sources(key: jax.Array, sources: Sequence[DataSource], k: int,
                     init: int = 1, max_rounds: int = 200, tol: float = 1e-3,
                     reg_covar: float = 1e-6, estep_backend: str = "auto",
                     chunk_size: int | None = None,
                     covariance_type: str = "diag") -> DEMResult:
    """Deprecated: ``repro.api.DEM(k).run(sources)`` dispatches on the
    input type, so the separate ``_from_sources`` spelling is obsolete.
    This shim forwards to the facade (bit-identical result) and will be
    removed."""
    warnings.warn(
        "dem_from_sources is deprecated; use repro.api.DEM(k).run(sources) "
        "— same engine, same bits",
        DeprecationWarning, stacklevel=2)
    from repro.api import DEM  # facade sits above core; lazy
    runner = DEM(k, config=FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_rounds, init=_legacy_init_name(init)))
    return runner.run(list(sources), key=key)
