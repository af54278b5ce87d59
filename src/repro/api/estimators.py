"""Estimator facades: one stable public surface over the training stack
(DESIGN.md §8).

Each facade holds exactly one validated :class:`FitConfig` and dispatches
on the *type* of the data it is handed — a resident ``(N, d)`` array, a
single out-of-core :class:`DataSource`, a padded federated
:class:`ClientSplit`, or a list of per-client sources — so the parallel
``*_streaming`` / ``*_source`` / ``*_from_sources`` entry-point families
of PRs 1–3 collapse into four classes:

=====================  ==================================================
facade                 accepted inputs
=====================  ==================================================
``GMMEstimator.fit``   ``(N, d)`` array · ``DataSource``
``KMeansEstimator.fit``  ``(N, d)`` array · ``DataSource``
``FedGenGMM.run``      ``ClientSplit`` · list of ``DataSource``
``DEM.run``            ``ClientSplit`` · list of ``DataSource``
=====================  ==================================================

The facades are thin by construction: they validate, resolve the PRNG key
from the config's seed policy, and call the cfg-core functions
(``fit_gmm_cfg`` & co.) — the same code the legacy keyword entry points
run — so facade fits are bit-identical to the pre-refactor entry points
for the same configuration.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.config import (FitConfig, is_source, is_source_list,
                               require_array_weights)
from repro.core.dem import DEMResult, _resolve_init, dem_cfg
from repro.core.em import (EMResult, bic_streaming, fit_gmm_bic_cfg,
                           fit_gmm_cfg, log_prob_chunked, score_streaming)
from repro.core.fedgen import FedGenResult, fedgengmm_cfg
from repro.core.gmm import GMM
from repro.core.kmeans import KMeansResult, kmeans_fit_cfg
from repro.core.partition import ClientSplit
from repro.core.privacy import DPConfig
from repro.fed.runtime import FederationStrategy, run_rounds
from repro.fed.transforms import GaussianDP
from repro.fed.strategies import (FedEMResult, FedKMeansResult,
                                  _resolve_fedkmeans_init, fed_kmeans_cfg,
                                  fedem_cfg)


def _make_config(config: Optional[FitConfig], overrides: dict) -> FitConfig:
    """One config per facade: an explicit ``FitConfig``, field overrides
    on top of it (or of the defaults), or both. Validation happens in
    ``FitConfig`` itself — exactly once, at construction."""
    cfg = config if config is not None else FitConfig()
    if not isinstance(cfg, FitConfig):
        raise TypeError(f"config must be a FitConfig, "
                        f"got {type(cfg).__name__}")
    if overrides:
        valid = {f.name for f in dataclasses.fields(FitConfig)}
        unknown = set(overrides) - valid
        if unknown:
            raise TypeError(
                f"unknown FitConfig field(s) {sorted(unknown)}; "
                f"valid fields: {sorted(valid)}")
        cfg = cfg.replace(**overrides)
    return cfg


_INPUT_NAMES = {"array": "an (N, d) array", "source": "a DataSource",
                "sources": "a list of per-client DataSources",
                "split": "a ClientSplit"}


def _accept_names(accept: tuple) -> str:
    return " or ".join(_INPUT_NAMES[a] for a in accept)


def _classify(data, who: str, accept: tuple) -> str:
    """THE input-type dispatch map (§8): array | source | sources | split,
    with a pointed error naming what ``who`` accepts."""
    if is_source(data):
        kind = "source"
    elif isinstance(data, ClientSplit):
        kind = "split"
    elif is_source_list(data):
        kind = "sources"
    elif isinstance(data, (list, tuple)):
        if not data:
            raise TypeError(
                f"{who}: got an empty {type(data).__name__} — "
                + ("need at least one client DataSource"
                   if "sources" in accept else
                   f"{who} accepts {_accept_names(accept)}"))
        if "sources" not in accept:
            raise TypeError(
                f"{who}: got a {type(data).__name__} — {who} accepts "
                f"{_accept_names(accept)}")
        raise TypeError(
            f"{who}: got a {type(data).__name__} that is not a list of "
            f"DataSources; federated clients must all be DataSource "
            f"instances (wrap resident shards in ArraySource)")
    elif hasattr(data, "shape") and hasattr(data, "ndim"):
        kind = "array"
    else:
        raise TypeError(
            f"{who}: cannot dispatch input of type {type(data).__name__}")
    if kind not in accept:
        raise TypeError(
            f"{who} accepts {_accept_names(accept)}, "
            f"got {_INPUT_NAMES[kind]}")
    return kind


def _check_weights(kind: str, sample_weight, who: str) -> None:
    """Satellite rule, enforced once at the facade boundary: sample
    weights are array-path-only by design."""
    if kind == "source":
        require_array_weights(sample_weight, who)


def _resolve_key(key: Optional[jax.Array], config: FitConfig) -> jax.Array:
    """Seed policy: an explicit key wins; otherwise the config's seed."""
    return config.key() if key is None else key


def _as_int(value, name: str, minimum: int = 1) -> int:
    """Same integral strictness as FitConfig's knobs: truncating k=3.7
    would mask division-gone-wrong caller bugs."""
    if isinstance(value, bool) or int(value) != value:
        raise ValueError(f"{name} must be an integer, got {value!r}")
    value = int(value)
    if value < minimum:
        raise ValueError(f"{name} must be >= {minimum}, got {value}")
    return value


# ----------------------------------------------------------------------
# Model-level scoring helpers (facade twins of the streaming scorers)
# ----------------------------------------------------------------------

def score(gmm: GMM, data, sample_weight=None,
          config: Optional[FitConfig] = None) -> jax.Array:
    """Average log-likelihood of ``data`` under ``gmm`` (the paper's
    fitness score, Eq. 2) — array or :class:`DataSource`, chunked per the
    config (O(chunk·K) memory with an integer ``chunk_size``)."""
    cfg = config if config is not None else FitConfig()
    kind = _classify(data, "repro.api.score", ("array", "source"))
    _check_weights(kind, sample_weight, "repro.api.score over a DataSource")
    return score_streaming(gmm, data, sample_weight,
                           chunk_size=cfg.resolve_chunk(kind == "source"),
                           backend=cfg.backend)


def log_prob(gmm: GMM, data, config: Optional[FitConfig] = None) -> jax.Array:
    """Per-row mixture log density -> (N,), chunked per the config (the
    anomaly-detection scorer; the (N, K) density block never exists)."""
    cfg = config if config is not None else FitConfig()
    kind = _classify(data, "repro.api.log_prob", ("array", "source"))
    return log_prob_chunked(gmm, data,
                            chunk_size=cfg.resolve_chunk(kind == "source"),
                            backend=cfg.backend)


def bic(gmm: GMM, data, sample_weight=None,
        config: Optional[FitConfig] = None) -> jax.Array:
    """Bayesian Information Criterion (lower is better), chunked per the
    config — what makes model selection over candidate K constant-memory."""
    cfg = config if config is not None else FitConfig()
    kind = _classify(data, "repro.api.bic", ("array", "source"))
    _check_weights(kind, sample_weight, "repro.api.bic over a DataSource")
    return bic_streaming(gmm, data, sample_weight,
                         chunk_size=cfg.resolve_chunk(kind == "source"),
                         backend=cfg.backend)


# ----------------------------------------------------------------------
# Single-model estimators
# ----------------------------------------------------------------------

class GMMEstimator:
    """EM-trained Gaussian mixture (the paper's TrainGMM, Algorithm 4.1).

    Fix ``k`` for a single fit, or pass ``k_candidates`` for BIC model
    selection (``bics_`` then holds every candidate's score). ``fit``
    accepts a resident ``(N, d)`` array or a :class:`DataSource` (init, EM
    and scoring then run out-of-core); after fitting, ``gmm_`` /
    ``result_`` hold the model and the full :class:`EMResult`.

        est = GMMEstimator(k=8, chunk_size=65536).fit(NpyFileSource(p))
        est.score(x_test)
    """

    def __init__(self, k: Optional[int] = None, *,
                 k_candidates: Optional[Sequence[int]] = None,
                 config: Optional[FitConfig] = None, **overrides):
        if (k is None) == (k_candidates is None):
            raise ValueError(
                "pass exactly one of k (single fit) or k_candidates "
                "(BIC model selection)")
        self.k = None if k is None else _as_int(k, "k")
        self.k_candidates = (None if k_candidates is None else tuple(
            _as_int(kc, "k_candidates entry") for kc in k_candidates))
        self.config = _make_config(config, overrides)
        if self.config.init not in ("auto", "kmeans"):
            raise ValueError(
                f"GMMEstimator init strategy must be 'auto' or 'kmeans' "
                f"(the DEM schemes do not apply), got {self.config.init!r}")
        self.gmm_: Optional[GMM] = None
        self.result_: Optional[EMResult] = None
        self.bics_: Optional[dict[int, float]] = None

    def fit(self, data, *, sample_weight=None,
            init_gmm: Optional[GMM] = None,
            key: Optional[jax.Array] = None) -> "GMMEstimator":
        """Fit on a resident ``(N, d)`` array or a :class:`DataSource`
        (out-of-core). ``sample_weight`` is per-row (resident data only);
        ``init_gmm`` warm-starts EM (exclusive with ``k_candidates``);
        ``key`` overrides the config's seed policy. Returns ``self``."""
        kind = _classify(data, "GMMEstimator.fit", ("array", "source"))
        _check_weights(kind, sample_weight,
                       "GMMEstimator.fit over a DataSource")
        if kind == "array":
            data = jnp.asarray(data)
        key = _resolve_key(key, self.config)
        if self.k_candidates is None:
            self.result_ = fit_gmm_cfg(key, data, self.k, self.config,
                                       sample_weight, init_gmm)
            self.bics_ = None
        else:
            if init_gmm is not None:
                raise ValueError("init_gmm and k_candidates are exclusive "
                                 "(each candidate K needs its own init)")
            self.result_, self.bics_ = fit_gmm_bic_cfg(
                key, data, self.k_candidates, self.config, sample_weight)
        self.gmm_ = self.result_.gmm
        return self

    # scoring rides the same config (backend + chunking) as the fit
    def _fitted(self) -> GMM:
        if self.gmm_ is None:
            raise RuntimeError("estimator is not fitted; call fit() first")
        return self.gmm_

    def score(self, data, sample_weight=None) -> jax.Array:
        """Average per-row log-likelihood of ``data`` (array or
        :class:`DataSource`) under the fitted model — a scalar."""
        return score(self._fitted(), data, sample_weight, self.config)

    def log_prob(self, data) -> jax.Array:
        """Per-row mixture log density under the fitted model -> (N,)."""
        return log_prob(self._fitted(), data, self.config)

    def bic(self, data, sample_weight=None) -> jax.Array:
        """Bayesian information criterion of the fitted model on ``data``
        (lower is better) — the model-selection score behind
        ``k_candidates``."""
        return bic(self._fitted(), data, sample_weight, self.config)


class KMeansEstimator:
    """Weighted Lloyd's algorithm with k-means++ seeding (also DEM init 3
    and the GMM init leg). ``n_init`` restarts keep the lowest-inertia
    centers. ``fit`` accepts a resident ``(N, d)`` array or a
    :class:`DataSource` (streamed seeding + host-loop sweeps;
    ``assignments_`` is then None — it would be the only O(N) output)."""

    def __init__(self, k: int, *, n_init: int = 1,
                 config: Optional[FitConfig] = None, **overrides):
        self.k = _as_int(k, "k")
        self.n_init = _as_int(n_init, "n_init")
        self.config = _make_config(config, overrides)
        if self.config.init not in ("auto", "kmeans"):
            raise ValueError(
                f"KMeansEstimator seeding is k-means++; init must stay "
                f"'auto' or 'kmeans', got {self.config.init!r}")
        self.result_: Optional[KMeansResult] = None

    def fit(self, data, *, sample_weight=None,
            key: Optional[jax.Array] = None) -> "KMeansEstimator":
        """Fit on a resident ``(N, d)`` array or a :class:`DataSource`.
        ``sample_weight`` is per-row (resident data only); ``key``
        overrides the config's seed policy. Returns ``self``."""
        kind = _classify(data, "KMeansEstimator.fit", ("array", "source"))
        _check_weights(kind, sample_weight,
                       "KMeansEstimator.fit over a DataSource")
        if kind == "array":
            data = jnp.asarray(data)
        key = _resolve_key(key, self.config)
        self.result_ = kmeans_fit_cfg(key, data, self.k, self.config,
                                      sample_weight, self.n_init)
        return self

    @property
    def centers_(self):
        """Fitted ``(k, d)`` cluster centers (best restart)."""
        if self.result_ is None:
            raise RuntimeError("estimator is not fitted; call fit() first")
        return self.result_.centers

    @property
    def assignments_(self):
        """Per-row cluster index ``(N,)`` — None after a DataSource fit
        (the only O(N) output is skipped out-of-core)."""
        if self.result_ is None:
            raise RuntimeError("estimator is not fitted; call fit() first")
        return self.result_.assignments

    @property
    def inertia_(self):
        """Weighted sum of squared distances to the assigned centers
        (the quantity ``n_init`` restarts minimize)."""
        if self.result_ is None:
            raise RuntimeError("estimator is not fitted; call fit() first")
        return self.result_.inertia


# ----------------------------------------------------------------------
# Federated runners
# ----------------------------------------------------------------------

class FedGenGMM:
    """The paper's one-shot federated pipeline (Algorithm 4.1): local EM
    per client, ONE communication round of (K, 2d+1) parameter blocks,
    server-side merge -> synthetic replay -> global refit.

    ``run(clients)`` dispatches on the client container: a padded
    :class:`ClientSplit` trains residents under vmap; a list of
    :class:`DataSource` streams every local fit and (by default,
    ``synthetic="auto"``) replays the merged mixture as a seeded block
    stream, so no stage holds O(N) rows. Returns a
    :class:`repro.core.fedgen.FedGenResult`.
    """

    def __init__(self, *, k_clients: Optional[int] = None,
                 k_global: Optional[int] = None,
                 k_candidates: Optional[Sequence[int]] = None,
                 h: int = 100, synthetic: str = "auto",
                 dp: Optional[DPConfig] = None, transform=None,
                 config: Optional[FitConfig] = None, **overrides):
        if k_clients is None and k_candidates is None:
            raise ValueError("pass k_clients (fixed local K) or "
                             "k_candidates (per-client BIC selection)")
        if k_global is None and k_candidates is None:
            raise ValueError("pass k_global (fixed global K) or "
                             "k_candidates (server-side BIC selection)")
        if synthetic not in ("auto", "resident", "source"):
            raise ValueError(f"synthetic must be 'auto', 'resident' or "
                             f"'source', got {synthetic!r}")
        self.k_clients = (None if k_clients is None
                          else _as_int(k_clients, "k_clients"))
        self.k_global = (None if k_global is None
                         else _as_int(k_global, "k_global"))
        self.k_candidates = (None if k_candidates is None else tuple(
            _as_int(kc, "k_candidates entry") for kc in k_candidates))
        self.h = _as_int(h, "h")
        self.synthetic = synthetic
        if dp is not None and transform is not None:
            raise ValueError(
                "pass dp (a DPConfig, sugar for a one-shot GaussianDP "
                "uplink transform) OR transform (any PayloadTransform), "
                "not both")
        if dp is not None:
            if not isinstance(dp, DPConfig):
                raise TypeError(
                    f"dp must be a DPConfig, got {type(dp).__name__}")
            transform = GaussianDP(epsilon=float(dp.epsilon),
                                   delta=float(dp.delta), rounds=1,
                                   min_count=float(dp.min_count))
        self.transform = transform
        self.config = _make_config(config, overrides)
        if self.config.init not in ("auto", "kmeans"):
            raise ValueError(
                f"FedGenGMM local fits use the k-means init; init must "
                f"stay 'auto' or 'kmeans' (the DEM schemes do not apply), "
                f"got {self.config.init!r}")
        self.result_: Optional[FedGenResult] = None

    def run(self, clients, *, key: Optional[jax.Array] = None) -> FedGenResult:
        """Run the one-shot pipeline over a :class:`ClientSplit` (vmapped
        residents) or a list of per-client :class:`DataSource`\\ s
        (streamed) -> :class:`repro.core.fedgen.FedGenResult`."""
        _classify(clients, "FedGenGMM.run", ("split", "sources"))
        key = _resolve_key(key, self.config)
        self.result_ = fedgengmm_cfg(
            key, clients, self.config, k_clients=self.k_clients,
            k_global=self.k_global, k_candidates=self.k_candidates,
            h=self.h, synthetic=self.synthetic, transform=self.transform)
        return self.result_

    @property
    def global_gmm_(self) -> GMM:
        """The merged-and-refit global mixture from the last ``run``."""
        if self.result_ is None:
            raise RuntimeError("runner has no result; call run() first")
        return self.result_.global_gmm


class DEM:
    """The iterative distributed-EM baseline (§5.4): one round of
    sufficient-statistics aggregation per EM iteration.

    ``run(clients)`` dispatches like :class:`FedGenGMM`; the init strategy
    comes from ``FitConfig.init`` ("auto" = fed-kmeans for splits,
    separated centers for source clients; "pilot" uploads raw rows and
    needs resident data). ``FitConfig.max_iter`` bounds the communication
    rounds. Returns a :class:`repro.core.dem.DEMResult`.

    ``mesh`` (a ``jax.sharding.Mesh`` with a ``"data"`` axis) shards a
    split's clients over the devices, ``C / shards`` each: the fed-kmeans
    init and every round's E-step run on each device's own clients, and
    each round ends in one all-reduce of the statistics (DESIGN.md §9).
    """

    def __init__(self, k: int, *, transform=None, async_policy=None,
                 mesh=None, config: Optional[FitConfig] = None,
                 **overrides):
        if mesh is not None and async_policy is not None:
            raise ValueError("DEM on a mesh runs the synchronous round "
                             "loop; async_policy takes no mesh")
        self.k = _as_int(k, "k")
        self.transform = transform
        self.async_policy = async_policy
        self.mesh = mesh
        self.config = _make_config(config, overrides)
        # one copy of the strategy rule: construction-time validation
        # delegates to the core resolver (input-type resolution of "auto"
        # happens per run(); "pilot" additionally needs resident data)
        _resolve_init(self.config.init, sources=False)
        self.result_: Optional[DEMResult] = None

    def run(self, clients, *, key: Optional[jax.Array] = None) -> DEMResult:
        """Run distributed EM to convergence (or ``max_iter`` rounds)
        over a :class:`ClientSplit` or list of per-client
        :class:`DataSource`\\ s -> :class:`repro.core.dem.DEMResult`.
        With an ``async_policy`` (:class:`repro.fed.AsyncPolicy`) the
        rounds run buffered-asynchronously (``repro.fed.run_async``).
        With a ``mesh`` the split's data and mask are placed over its
        ``"data"`` axis first, which costs nothing where they already lie
        so; the client count must divide by the axis' size."""
        _classify(clients, "DEM.run",
                  ("split",) if self.mesh is not None else
                  ("split", "sources"))
        key = _resolve_key(key, self.config)
        self.result_ = dem_cfg(key, clients, self.config, self.k,
                               transform=self.transform,
                               async_policy=self.async_policy,
                               mesh=self.mesh)
        return self.result_

    @property
    def global_gmm_(self) -> GMM:
        """The converged global mixture from the last ``run``."""
        if self.result_ is None:
            raise RuntimeError("runner has no result; call run() first")
        return self.result_.global_gmm


class FedEM:
    """Iterative federated EM (Tian et al.): per round, each participating
    client runs ``local_epochs`` local EM steps from the broadcast
    parameters and ships sufficient statistics; the server M-steps. With
    the default knobs this IS the DEM baseline bit for bit; the knobs are
    what stage the paper's accuracy-vs-communication comparison under
    realistic client availability.

    ``run(clients)`` dispatches like :class:`DEM` (ClientSplit or list of
    per-client DataSources; the sharded-mesh variant is
    ``repro.distributed.fedem_sharded``). ``participation`` in (0, 1] is
    the per-round cohort fraction; ``cohort`` picks how the driver
    samples it — ``"cyclic"`` (deterministic window, never empty, covers
    every client) or ``"uniform"`` (seeded sampling without replacement,
    ``cohort_seed``) — and ONLY the sampled clients compute, so a round
    costs O(cohort). ``stragglers`` (an
    :class:`repro.fed.ArrivalStragglers` or any ``drop_mask`` policy)
    drops each round's slowest arrivals to exact-zero contribution.
    ``local_epochs >= 1`` is the client-side EM steps per round. Init
    comes from ``FitConfig.init`` exactly as in DEM. Returns a
    :class:`repro.fed.strategies.FedEMResult` with the populated
    cohort-sized communication ledger (init-phase warm-start traffic
    included).
    """

    def __init__(self, k: int, *, participation: float = 1.0,
                 local_epochs: int = 1, cohort: str = "cyclic",
                 cohort_seed: int = 0, stragglers=None, transform=None,
                 async_policy=None, config: Optional[FitConfig] = None,
                 **overrides):
        self.k = _as_int(k, "k")
        if not 0.0 < float(participation) <= 1.0:
            raise ValueError(
                f"participation must be in (0, 1], got {participation}")
        if cohort not in ("cyclic", "uniform"):
            raise ValueError(
                f"cohort must be 'cyclic' or 'uniform', got {cohort!r}")
        self.participation = float(participation)
        self.local_epochs = _as_int(local_epochs, "local_epochs")
        self.cohort = cohort
        self.cohort_seed = _as_int(cohort_seed, "cohort_seed", minimum=0)
        self.stragglers = stragglers
        self.transform = transform
        self.async_policy = async_policy
        self.config = _make_config(config, overrides)
        # same strategy rule as DEM: validate the init scheme name now,
        # resolve "auto" per input type at run()
        _resolve_init(self.config.init, sources=False)
        self.result_: Optional[FedEMResult] = None

    def run(self, clients, *, key: Optional[jax.Array] = None) -> FedEMResult:
        """Run federated EM under the configured participation/cohort/
        straggler policy -> :class:`repro.fed.strategies.FedEMResult`
        (with the cohort-sized communication ledger)."""
        _classify(clients, "FedEM.run", ("split", "sources"))
        key = _resolve_key(key, self.config)
        self.result_ = fedem_cfg(key, clients, self.config, self.k,
                                 participation=self.participation,
                                 local_epochs=self.local_epochs,
                                 cohort=self.cohort,
                                 cohort_seed=self.cohort_seed,
                                 stragglers=self.stragglers,
                                 transform=self.transform,
                                 async_policy=self.async_policy)
        return self.result_

    @property
    def global_gmm_(self) -> GMM:
        """The final broadcast mixture from the last ``run``."""
        if self.result_ is None:
            raise RuntimeError("runner has no result; call run() first")
        return self.result_.global_gmm


class FedKMeans:
    """Iterative federated k-means (Garst et al.): per round, clients ship
    per-center label statistics (counts, sums, inertia) against the
    broadcast centers; the server recombines into new centers and stops on
    the squared center shift (``FitConfig.tol``, resolving through the
    k-means defaults — 1e-4 / 100 rounds).

    ``run(clients)`` dispatches like the other federated runners
    (sharded-mesh variant: ``repro.distributed.fed_kmeans_sharded``).
    ``FitConfig.init`` is "auto"/"fed-kmeans" (one-shot warm start,
    Dennis et al. '21) or "separated". Returns a
    :class:`repro.fed.strategies.FedKMeansResult`.
    """

    def __init__(self, k: int, *, transform=None,
                 config: Optional[FitConfig] = None, **overrides):
        self.k = _as_int(k, "k")
        self.transform = transform
        self.config = _make_config(config, overrides)
        _resolve_fedkmeans_init(self.config.init)
        self.result_: Optional[FedKMeansResult] = None

    def run(self, clients, *,
            key: Optional[jax.Array] = None) -> FedKMeansResult:
        """Run federated k-means to center convergence (or the round
        budget) -> :class:`repro.fed.strategies.FedKMeansResult`."""
        _classify(clients, "FedKMeans.run", ("split", "sources"))
        key = _resolve_key(key, self.config)
        self.result_ = fed_kmeans_cfg(key, clients, self.config, self.k,
                                      transform=self.transform)
        return self.result_

    @property
    def centers_(self):
        """The final ``(k, d)`` global centers from the last ``run``."""
        if self.result_ is None:
            raise RuntimeError("runner has no result; call run() first")
        return self.result_.centers


# The four named strategies of the §9 runtime, as facade constructors.
_STRATEGY_RUNNERS = {"fedgen": FedGenGMM, "dem": DEM, "fedem": FedEM,
                     "fedkmeans": FedKMeans}


def fit_federated(clients, *, strategy, key: Optional[jax.Array] = None,
                  config: Optional[FitConfig] = None, max_rounds=None,
                  sampler=None, stragglers=None, transform=None,
                  async_policy=None, **kwargs):
    """THE strategy seam for FitConfig-driven federated runs (§9).

    ``strategy`` is either a name — ``"fedgen"`` | ``"dem"`` | ``"fedem"``
    | ``"fedkmeans"`` — whose facade is constructed from ``config`` plus
    the remaining keyword arguments (``k=...``, ``participation=...``,
    ...), or a custom :class:`repro.fed.FederationStrategy` instance,
    which runs directly on the round driver (``max_rounds`` then bounds
    it; default: the config's EM round budget). Custom strategies also
    take the driver's cohort-execution seams directly: ``sampler`` (a
    ``repro.fed.cohort`` sampler — each round computes only its sampled
    cohort) and ``stragglers`` (a ``drop_mask`` policy). Named
    strategies express the same knobs through their own keywords
    (``participation=...``, ``cohort=...``, ``stragglers=...`` for
    FedEM). Scenario PRs plug in HERE: a new baseline is one strategy
    class, not a new entry-point family.

    ``transform`` installs an uplink :class:`repro.fed.PayloadTransform`
    (§11) — :class:`~repro.fed.GaussianDP`,
    :class:`~repro.fed.StochasticQuantize`,
    :class:`~repro.fed.PairwiseMask`, a :class:`~repro.fed.Compose` of
    them, or anything implementing the protocol — applied to every
    client's payload before the server aggregate, on every backend and
    for named and custom strategies alike.

    ``async_policy`` (a :class:`repro.fed.AsyncPolicy`) reroutes the
    round loop through the buffered asynchronous driver
    (``repro.fed.run_async``, §12): the server combines every
    ``buffer_size`` updates under the staleness-weighting rule instead
    of waiting for the full cohort. It applies to the iterative
    strategies — ``"dem"`` / ``"fedem"`` by name, or any custom
    iterative :class:`~repro.fed.FederationStrategy`.
    """
    if isinstance(strategy, str):
        if strategy not in _STRATEGY_RUNNERS:
            raise ValueError(
                f"unknown strategy {strategy!r}; named strategies are "
                f"{sorted(_STRATEGY_RUNNERS)} (or pass a "
                f"FederationStrategy instance)")
        if max_rounds is not None:
            raise TypeError(
                "max_rounds is for custom FederationStrategy instances; "
                "named strategies take FitConfig.max_iter")
        if sampler is not None:
            raise TypeError(
                "sampler is for custom FederationStrategy instances; "
                "named strategies build their own (FedEM: participation="
                "... with cohort='cyclic'|'uniform')")
        if stragglers is not None:
            kwargs["stragglers"] = stragglers
        if transform is not None:
            kwargs["transform"] = transform
        if async_policy is not None:
            if strategy not in ("dem", "fedem"):
                raise TypeError(
                    f"async_policy applies to the iterative strategies "
                    f"('dem', 'fedem'), not {strategy!r}")
            kwargs["async_policy"] = async_policy
        runner = _STRATEGY_RUNNERS[strategy](config=config, **kwargs)
        return runner.run(clients, key=key)
    if not isinstance(strategy, FederationStrategy):
        raise TypeError(
            f"strategy must be a name or a FederationStrategy "
            f"(local_step/server_combine/converged/...), got "
            f"{type(strategy).__name__}")
    if kwargs:
        raise TypeError(
            f"unknown argument(s) for a custom strategy run: "
            f"{sorted(kwargs)}")
    cfg = config if config is not None else FitConfig()
    if max_rounds is None:
        max_rounds = 1 if getattr(strategy, "one_shot", False) \
            else cfg.resolve_max_iter("em")
    key = _resolve_key(key, cfg)
    if async_policy is not None:
        from repro.fed.async_runtime import run_async
        return run_async(strategy, clients, key=key, max_rounds=max_rounds,
                         sampler=sampler, stragglers=stragglers,
                         transform=transform,
                         **async_policy.driver_kwargs())
    return run_rounds(strategy, clients, key=key, max_rounds=max_rounds,
                      sampler=sampler, stragglers=stragglers,
                      transform=transform)
