"""The iterative federated baselines the ROADMAP names, as strategies on
the federation runtime (DESIGN.md §9).

- :class:`FedEMStrategy` — iterative federated EM after Tian et al.
  (non-asymptotic analysis of federated EM): per round, each
  participating client runs ``local_epochs`` local EM steps from the
  current global parameters and ships its final-epoch
  ``SufficientStats``; the server sums and M-steps. With
  ``participation=1.0`` and ``local_epochs=1`` this IS the DEM baseline
  (``repro.core.dem``) — literally, it subclasses :class:`DEMStrategy`
  and the reduction is pinned bit-for-bit in
  ``tests/test_fed_runtime.py``. Partial participation is COHORT
  EXECUTION (``repro.fed.cohort``): the driver samples
  ``max(1, round(participation·C))`` clients per round — the default
  cyclic sampler is deterministic, non-empty, covers every client, and
  is pinned bit-identical to the historical train-all + zero-mask path;
  a seeded uniform sampler is one knob away — and ONLY the cohort
  computes, so a round costs O(cohort), not O(population).

- :class:`FedKMeansStrategy` — iterative federated k-means after Garst &
  Reinders: per round, each client assigns its rows to the current global
  centers and ships per-center label statistics (counts, sums, inertia —
  the existing ``lloyd_round_stats`` machinery); the server recombines
  into new centers and stops on the squared center shift. Init is a
  one-shot federated k-means warm start (Dennis et al. '21) or the
  "separated" scheme.

Both run under every client backend — padded :class:`ClientSplit`, list
of per-client :class:`DataSource` streams, or a sharded mesh
(``repro.distributed.fedem_sharded`` / ``fed_kmeans_sharded``) — with
populated communication ledgers, because :func:`~repro.fed.runtime.
run_rounds` owns all of that.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.config import FitConfig, is_source_list, resolve_backend
from repro.core.dem import DEMStrategy, _resolve_init, max_separated_centers
from repro.core.em import computed_lanes, e_step_stats, m_step
from repro.core.gmm import GMM
from repro.core.kmeans import (federated_kmeans, federated_kmeans_sharded,
                               gathered_floats, lloyd_round_stats)
from repro.core.partition import ClientSplit
from repro.fed.cohort import make_sampler
from repro.fed.ledger import (CommStats, RoundPayload, dtype_itemsize,
                              label_payload_floats)
from repro.fed.runtime import run_rounds


class FedEMResult(NamedTuple):
    global_gmm: GMM
    log_likelihood: jax.Array   # avg loglik over the last round's cohort
    n_rounds: jax.Array
    converged: jax.Array
    comm: CommStats


class FedEMState(NamedTuple):
    """DEM's round state plus the round counter that drives the cyclic
    participation window and the per-cohort loglik history that makes
    partial-participation convergence judgeable (see
    :meth:`FedEMStrategy._next_state`)."""
    gmm: GMM
    prev_ll: jax.Array
    ll: jax.Array
    tol: jax.Array
    reg_covar: jax.Array
    rnd: jax.Array
    ll_hist: jax.Array   # (T,) ring buffer, T = cohort cycle length


@dataclasses.dataclass(frozen=True)
class FedEMStrategy(DEMStrategy):
    """DEM generalized per Tian et al.: ``local_epochs`` local EM steps
    per round (clients M-step on their own stats between E-steps and ship
    the final epoch's statistics) and partial participation
    (``participation`` fraction of clients per round). Defaults reduce it
    to :class:`DEMStrategy` exactly.

    Since the cohort-execution refactor WHICH clients run is not this
    strategy's business: the driver's sampler (``run_rounds(sampler=...)``,
    built by :func:`fedem_cfg`) hands each backend the round's cohort and
    only those clients compute. The knobs here still size the
    convergence machinery: ``participation``/``n_clients`` fix the
    cohort-cycle length of the loglik ring buffer."""

    participation: float = 1.0
    local_epochs: int = 1
    n_clients: int = 0   # required when participation < 1 (cycle length)

    name = "fedem"

    def __post_init__(self):
        if not 0.0 < float(self.participation) <= 1.0:
            raise ValueError(
                f"participation must be in (0, 1], got {self.participation}")
        if int(self.local_epochs) < 1:
            raise ValueError(
                f"local_epochs must be >= 1, got {self.local_epochs}")
        if self.participation < 1.0 and self.n_clients < 1:
            raise ValueError(
                "participation < 1 needs n_clients (the cyclic cohort "
                "window is sized from it); the cfg-cores fill it from the "
                "client container")

    def cohort_size(self) -> int:
        """Clients per round under cyclic participation (always >= 1)."""
        if self.participation >= 1.0:
            return self.n_clients
        return max(1, int(round(self.participation * self.n_clients)))

    def _period(self) -> int:
        """Rounds until the cyclic window revisits the same cohort: the
        additive order of the window stride ``m`` in Z_C, i.e.
        C / gcd(C, m). 1 under full participation."""
        if self.participation >= 1.0:
            return 1
        c, m = self.n_clients, self.cohort_size()
        return c // math.gcd(c, m)

    def _make_state(self, gmm, prev_ll, ll, tol, reg_covar):
        rnd = 0 if self.host else jnp.array(0)
        hist = jnp.full((self._period(),), -jnp.inf, gmm.means.dtype)
        return FedEMState(gmm, prev_ll, ll, tol, reg_covar, rnd, hist)

    def _next_state(self, state, gmm, ll):
        t = self._period()
        if t == 1:
            # full participation: exactly DEM's consecutive-round delta
            return FedEMState(gmm, state.ll, ll, state.tol, state.reg_covar,
                              state.rnd + 1, state.ll_hist)
        # Partial participation: consecutive rounds score DIFFERENT
        # cohorts, so their loglik delta never settles below tol and the
        # loop used to run to max_iter every time (the PR-5 caveat). The
        # ring buffer makes prev_ll "this same cohort's loglik one cycle
        # ago" — a like-for-like delta the inherited DEM predicates
        # (|ll - prev_ll| vs tol) can judge. Slots still at -inf (first
        # cycle) keep the loop going unconditionally.
        pos = state.rnd % t
        prev = state.ll_hist[pos]
        hist = state.ll_hist.at[pos].set(ll)
        if self.host:
            prev = float(prev)
        return FedEMState(gmm, prev, ll, state.tol, state.reg_covar,
                          state.rnd + 1, hist)

    def local_step(self, state: FedEMState, x, w, idx):
        """One cohort member's update. Participation is NOT handled here
        any more — the driver's sampler decides who runs and the backend
        computes only those clients (the historical per-client window
        test and the host-path skip both became driver/backend concerns;
        the uplink of a non-member is exactly absent, which the pinned
        zero-uplink ledger and e-step-count tests still assert)."""
        gmm = state.gmm
        stats = e_step_stats(gmm, x, w, self.backend, self.chunk)
        for _ in range(self.local_epochs - 1):
            gmm = m_step(stats, state.reg_covar)
            stats = e_step_stats(gmm, x, w, self.backend, self.chunk)
        return stats

    # round_payload is inherited from DEMStrategy: under a sampler the
    # driver's accounting view already reports num_clients == cohort
    # size, so the per-round arithmetic stays cohort-sized for free.

    def finalize(self, state: FedEMState, n_rounds, converged,
                 comm: CommStats) -> FedEMResult:
        ll = state.ll
        if self.host:
            ll = jnp.asarray(ll, state.gmm.means.dtype)
        return FedEMResult(state.gmm, ll, n_rounds, jnp.asarray(converged),
                           comm)


def fedem_cfg(key: jax.Array, clients, config: FitConfig, k: int,
              participation: float = 1.0, local_epochs: int = 1,
              cohort: str = "cyclic", cohort_seed: int = 0,
              stragglers=None, transform=None,
              async_policy=None) -> FedEMResult:
    """Run FedEM — the cfg-core behind ``repro.api.FedEM``, dispatching on
    the client input type through the federation runtime. Init strategies
    and their resolution are DEM's (``config.init``).

    ``participation < 1`` builds the driver-side cohort sampler
    (``cohort``: "cyclic" — the historical deterministic window — or
    "uniform" — seeded sampling without replacement from
    ``cohort_seed``); at full participation no sampler is installed, so
    the run reduces to DEM's full-population path bit for bit.
    ``stragglers`` (e.g. :class:`repro.fed.cohort.ArrivalStragglers`)
    drops each round's slowest arrivals. ``async_policy`` (a
    :class:`repro.fed.AsyncPolicy`) reroutes the rounds through the
    buffered asynchronous driver (``repro.fed.run_async``, DESIGN.md
    §12) — the server combines every ``buffer_size`` updates under the
    staleness-weighting rule instead of waiting for the whole cohort;
    None keeps the synchronous loop."""
    sources = is_source_list(clients)
    if not sources and not isinstance(clients, ClientSplit):
        raise TypeError(
            f"fedem clients must be a ClientSplit or a list of "
            f"DataSources, got {type(clients).__name__}")
    if not 0.0 < float(participation) <= 1.0:
        raise ValueError(
            f"participation must be in (0, 1], got {participation}")
    n_clients = len(clients) if sources else clients.data.shape[0]
    strategy = FedEMStrategy(
        k=k, covariance_type=config.covariance_type, backend=config.backend,
        chunk=config.resolve_chunk(source=sources),
        init=_resolve_init(config.init, sources), host=sources,
        tol=config.resolve_tol("em"), reg_covar=config.reg_covar,
        participation=float(participation), local_epochs=int(local_epochs),
        n_clients=n_clients)
    sampler = None
    if strategy.participation < 1.0:
        sampler = make_sampler(cohort, n_clients, strategy.cohort_size(),
                               seed=cohort_seed)
    elif cohort not in ("cyclic", "uniform"):
        raise ValueError(
            f"cohort sampler must be 'cyclic' or 'uniform', got {cohort!r}")
    if async_policy is not None:
        from repro.fed.async_runtime import run_async
        return run_async(strategy, clients, key=key,
                         max_rounds=config.resolve_max_iter("em"),
                         sampler=sampler, stragglers=stragglers,
                         transform=transform,
                         **async_policy.driver_kwargs())
    return run_rounds(strategy, clients, key=key,
                      max_rounds=config.resolve_max_iter("em"),
                      sampler=sampler, stragglers=stragglers,
                      transform=transform)


# ----------------------------------------------------------------------
# Federated k-means (Garst et al.)
# ----------------------------------------------------------------------

class FedKMeansResult(NamedTuple):
    centers: jax.Array        # (K, d) global centers
    inertia: jax.Array        # weighted inertia of the RETURNED centers:
    #                           one extra streamed assignment pass after
    #                           the last round (clients ship one scalar
    #                           each — accounted in comm as
    #                           extra_uplink_floats)
    n_rounds: jax.Array
    converged: jax.Array
    comm: CommStats


class FedKMeansState(NamedTuple):
    centers: jax.Array
    shift: jax.Array          # squared center shift of the last update
    inertia: jax.Array
    tol: jax.Array


FEDKMEANS_INITS = ("fed-kmeans", "separated")


@dataclasses.dataclass(frozen=True)
class FedKMeansStrategy:
    """Iterative federated Lloyd: clients ship per-center label statistics
    (counts, sums, inertia) against the broadcast centers; the server
    recombines ``sums/counts`` into new centers — a k-means M-step from
    summed hard-assignment statistics, exactly the EM pattern with
    responsibilities replaced by labels. Stops when the squared center
    shift drops to ``tol`` (the k-means convergence rule, so ``tol``
    resolves through the "kmeans" defaults)."""

    k: int
    assign_backend: str = "reference"   # resolved (never "auto") — this
    #                                     rides into jitted client steps
    chunk: Optional[int] = None
    init: str = "fed-kmeans"
    host: bool = False
    tol: float = dataclasses.field(default=1e-4, compare=False)

    one_shot = False
    name = "fedkmeans"

    def init_state(self, key: jax.Array, backend) -> FedKMeansState:
        k_init, _ = jax.random.split(key)
        if self.init == "separated":
            centers = max_separated_centers(k_init, self.k, backend.dim)
        elif backend.kind == "sources":
            centers = federated_kmeans(k_init, list(backend.sources), self.k,
                                       chunk_size=self.chunk)
        elif backend.kind == "sharded":
            centers = federated_kmeans_sharded(
                k_init, backend.data, backend.mask, mesh=backend.mesh,
                k_global=self.k, axis=backend.axis, chunk_size=self.chunk)
        else:
            centers = federated_kmeans(k_init, backend.data, self.k,
                                       client_weights=backend.mask,
                                       chunk_size=self.chunk)
        if self.host:
            return FedKMeansState(centers, float("inf"), float("inf"),
                                  float(self.tol))
        dt = centers.dtype
        inf = jnp.array(jnp.inf, dt)
        return FedKMeansState(centers, inf, inf, jnp.asarray(self.tol, dt))

    def lanes_computed(self, d: int) -> int:
        """Feature width a client's assignment sweep computes over."""
        return computed_lanes(d, self.assign_backend)

    # the fed-kmeans warm start gathers as DEM's does
    gathered_bytes = DEMStrategy.gathered_bytes

    def local_step(self, state: FedKMeansState, x, w, idx):
        return lloyd_round_stats(state.centers, x, w, self.assign_backend,
                                 self.chunk)

    def server_combine(self, state: FedKMeansState,
                       total) -> FedKMeansState:
        counts, sums, inertia = total
        new_centers = jnp.where(
            counts[:, None] > 0,
            sums / jnp.maximum(counts[:, None], 1e-12), state.centers)
        shift = jnp.sum((new_centers - state.centers) ** 2)
        if self.host:
            shift, inertia = float(shift), float(inertia)
        return FedKMeansState(new_centers, shift, inertia, state.tol)

    def converged(self, state: FedKMeansState):
        return state.shift <= state.tol

    def keep_going(self, state: FedKMeansState):
        """Distinct from ``not converged`` so a NaN center shift
        (degenerate geometry) halts the loop AND reports not-converged,
        like the EM loops."""
        return state.shift > state.tol

    def post_rounds(self, state: FedKMeansState, backend) -> FedKMeansState:
        """One extra assignment sweep against the FINAL centers, so the
        reported inertia describes the centers the caller gets. The round
        loop's own inertia scores the pre-update centers (the same bug
        class PR 2 fixed in ``kmeans``); each client ships one scalar
        back, accounted as ``extra_uplink_floats``."""

        def rescore(st, x, w, idx):
            _, _, inertia = lloyd_round_stats(st.centers, x, w,
                                              self.assign_backend, self.chunk)
            return inertia

        inertia = backend.reduce_clients(rescore, state)
        if self.host:
            inertia = float(inertia)
        return state._replace(inertia=inertia)

    def round_payload(self, backend, state) -> RoundPayload:
        c, d = backend.num_clients, backend.dim
        pop = getattr(backend, "population_clients", c)
        # Init-phase traffic rides the ledger too (warm starts are not
        # free): every scheme broadcasts the k·d round-0 centers to the
        # population; the fed-kmeans warm start first collects each
        # client's k local centers + k cluster sizes (Dennis et al.).
        warm_up = gathered_floats(pop, self.k, d) \
            if self.init == "fed-kmeans" else 0
        return RoundPayload(
            uplink_floats=c * label_payload_floats(self.k, d),
            downlink_floats=c * self.k * d,
            itemsize=dtype_itemsize(state.centers.dtype),
            # post-rounds inertia rescore (one scalar per population
            # client) + the warm-start statistics
            extra_uplink_floats=pop + warm_up,
            extra_downlink_floats=pop * self.k * d)

    def finalize(self, state: FedKMeansState, n_rounds, converged,
                 comm: CommStats) -> FedKMeansResult:
        inertia = state.inertia
        if self.host:
            inertia = jnp.asarray(inertia, state.centers.dtype)
        return FedKMeansResult(state.centers, inertia, n_rounds,
                               jnp.asarray(converged), comm)


def _resolve_fedkmeans_init(init: str) -> str:
    if init == "auto":
        return "fed-kmeans"
    if init not in FEDKMEANS_INITS:
        raise ValueError(
            f"FedKMeans init must be 'auto' or one of {FEDKMEANS_INITS} "
            f"(a one-shot warm start or separated centers), got {init!r}")
    return init


def fed_kmeans_cfg(key: jax.Array, clients, config: FitConfig,
                   k: int, transform=None) -> FedKMeansResult:
    """Run iterative federated k-means — the cfg-core behind
    ``repro.api.FedKMeans``, dispatching on the client input type through
    the federation runtime."""
    sources = is_source_list(clients)
    if not sources and not isinstance(clients, ClientSplit):
        raise TypeError(
            f"federated k-means clients must be a ClientSplit or a list "
            f"of DataSources, got {type(clients).__name__}")
    strategy = FedKMeansStrategy(
        k=k, assign_backend=resolve_backend(config.backend),
        chunk=config.resolve_chunk(source=sources),
        init=_resolve_fedkmeans_init(config.init), host=sources,
        tol=config.resolve_tol("kmeans"))
    return run_rounds(strategy, clients, key=key,
                      max_rounds=config.resolve_max_iter("kmeans"),
                      transform=transform)
