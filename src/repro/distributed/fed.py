"""Federated GMM learning as mesh collectives (DESIGN.md §3/§9).

Clients map to shards of the ``data`` mesh axis. The algorithms become
collective patterns:

  FedGenGMM (one-shot):  local EM runs with ZERO cross-shard communication,
      then the single communication round of the paper is literally ONE
      jax.lax.all_gather of the (K, 2d+1) parameter blocks + dataset sizes.
      The server-side merge/sample/refit then runs on the mesh's first
      device, as one parameter server would (outside shard_map, a
      mesh-replicated refit would ask the TPU compiler to partition its
      Pallas kernels, which it refuses).

  DEM / FedEM / FedKMeans (iterative): every round psums the per-client
      payload (EM sufficient statistics, or k-means label statistics)
      across the data axis — one all-reduce PER ROUND. The dry-run
      collective analysis makes Table 4 visible in HLO bytes.

Since the §9 refactor the iterative entry points here carry NO round loop
of their own: shard_map is just a *client backend*
(``repro.fed.runtime.ShardedClients`` — vmap over the shard's clients,
psum across the axis) under the same ``run_rounds`` driver that runs the
single-process strategies, so the mesh runtime and the reference
semantics cannot drift apart.

Client counts larger than the axis size are handled by placing multiple
clients per shard (the client axis is reshaped to (shards, per_shard)).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.config import FitConfig, resolve_backend
from repro.core.dem import DEMStrategy, _resolve_init
from repro.core.em import fit_gmm_cfg, init_from_means_sharded
from repro.core.gmm import GMM, merge_gmms_stacked
from repro.data.sources import SyntheticGMMSource
from repro.fed.cohort import make_sampler
from repro.fed.runtime import make_backend, run_rounds
from repro.fed.strategies import (FedEMResult, FedEMStrategy,
                                  FedKMeansResult, FedKMeansStrategy,
                                  _resolve_fedkmeans_init)


class ShardedFedResult(NamedTuple):
    global_gmm: GMM
    local_weights: jax.Array   # (C, K)
    local_means: jax.Array     # (C, K, d)
    local_covs: jax.Array      # (C, K, d)


def fedgen_sharded(mesh, key, data, mask, k: int, k_global: int,
                   h: int = 100, max_iter: int = 200, tol: float = 1e-3,
                   estep_backend: str = "auto",
                   chunk_size: int | None = None,
                   synthetic: str = "resident",
                   config: FitConfig | None = None):
    """One-shot FedGenGMM over a device mesh.

    data: (C, N, d), mask: (C, N) with C divisible by the data-axis size.
    Returns ShardedFedResult, on the mesh's first device (the server).
    ``config`` (a :class:`FitConfig`) selects the E-step engine for both
    the per-shard local fits and the server refit; the loose
    ``max_iter``/``tol``/``estep_backend``/``chunk_size`` knobs are the
    legacy spelling and are folded into one config (ignored when
    ``config`` is given).

    ``synthetic="source"`` makes the server refit out-of-core:
    the synthetic replay set |S| = H·K·C — the one dataset in this runtime
    that *grows with the client count* — is consumed as a seeded
    :class:`SyntheticGMMSource` block stream instead of being materialized
    (DESIGN.md §7). The collective pattern is untouched: the all_gather
    payload is parameters either way.
    """
    if synthetic not in ("resident", "source"):
        raise ValueError(f"synthetic must be 'resident' or 'source', "
                         f"got {synthetic!r}")
    cfg = config if config is not None else FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size, tol=tol,
        max_iter=max_iter)
    axis = "data"
    n_shards = mesh.shape[axis]
    c = data.shape[0]
    assert c % n_shards == 0, (c, n_shards)

    def local_part(keys, data_shard, mask_shard):
        """Runs per shard: train this shard's clients, no communication."""
        def one(kk, x, w):
            res = fit_gmm_cfg(kk, x, k, cfg, sample_weight=w)
            return res.gmm.weights, res.gmm.means, res.gmm.covs

        w, mu, cov = jax.vmap(one)(keys, data_shard, mask_shard)
        sizes = jnp.sum(mask_shard, axis=1)
        # === THE single communication round of the paper ===
        w_all = jax.lax.all_gather(w, axis, tiled=True)
        mu_all = jax.lax.all_gather(mu, axis, tiled=True)
        cov_all = jax.lax.all_gather(cov, axis, tiled=True)
        sz_all = jax.lax.all_gather(sizes, axis, tiled=True)
        return w_all, mu_all, cov_all, sz_all

    # The same key schedule as the single-process FedGenStrategy (one key
    # per client, then sample/refit keys), so a mesh run reproduces the
    # unsharded pipeline for the same key.
    k_local, k_agg = jax.random.split(key)
    spec = P(axis)
    fn = jax.shard_map(local_part, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=(P(), P(), P(), P()), check_vma=False)
    w_all, mu_all, cov_all, sz_all = fn(jax.random.split(k_local, c), data,
                                        mask)

    # server side, on one device: merge -> sample -> refit. The gathered
    # blocks go through the host, which drops their mesh type (a direct
    # device_put keeps the explicit mesh axes in later avals).
    w_all, mu_all, cov_all, sz_all = jax.device_put(
        jax.device_get((w_all, mu_all, cov_all, sz_all)),
        mesh.devices.flat[0])
    merged = merge_gmms_stacked(w_all, mu_all, cov_all, sz_all)
    n_synth = h * k * c
    k_sample, k_fit = jax.random.split(k_agg)
    if synthetic == "source":
        synth = SyntheticGMMSource(merged, n_synth, k_sample)
    else:
        synth = merged.sample(k_sample, n_synth)
    res = fit_gmm_cfg(k_fit, synth, k_global, cfg)
    return ShardedFedResult(res.gmm, w_all, mu_all, cov_all)


def dem_sharded(mesh, key, data, mask, k: int, init_centers=None,
                max_rounds: int = 100, tol: float = 1e-3,
                reg_covar: float = 1e-6,
                estep_backend: str = "auto",
                chunk_size: int | None = None,
                config: FitConfig | None = None,
                transform=None) -> tuple[GMM, jax.Array]:
    """Distributed EM over the mesh: one psum of sufficient statistics per
    EM round (the iterative baseline's communication pattern).

    The keyword spelling of ``repro.api.DEM(k, mesh=mesh)``: a
    :class:`~repro.core.dem.DEMStrategy` on the shared round driver with
    shard_map as the client backend. ``init_centers`` overrides the
    strategy's init with caller-chosen global centers (the data moments
    around them are still computed on the shards); without them ``key``
    seeds the init ``config.init`` names, as in ``repro.api.DEM``. With an
    integer chunk size each shard streams its clients' rows through the
    engine so per-round shard memory is bounded by (chunk_size, K) rather
    than (N, K) — the psum payload is unchanged (SufficientStats is
    already the reduced form).
    """
    cfg = config if config is not None else FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size, tol=tol,
        max_iter=max_rounds, reg_covar=reg_covar)
    strategy = DEMStrategy(
        k=k, covariance_type=cfg.covariance_type, backend=cfg.backend,
        chunk=cfg.resolve_chunk(source=False),
        init=_resolve_init(cfg.init, sources=False), host=False,
        tol=cfg.resolve_tol("em"), reg_covar=cfg.reg_covar)
    backend = make_backend((data, mask), mesh)
    res = run_rounds(strategy, (backend.data, backend.mask), key=key,
                     mesh=mesh, state0=_state_around(strategy, backend,
                                                     init_centers, cfg),
                     max_rounds=cfg.resolve_max_iter("em"),
                     transform=transform)
    return res.global_gmm, res.n_rounds


def _state_around(strategy, backend, init_centers, cfg):
    """Round-0 state around caller-chosen centers, with the data moments
    computed on the shards; None (the strategy's own init) without
    them."""
    if init_centers is None:
        return None
    gmm0 = init_from_means_sharded(
        jnp.asarray(init_centers), backend.data, backend.mask,
        mesh=backend.mesh, axis=backend.axis,
        covariance_type=cfg.covariance_type, reg_covar=cfg.reg_covar)
    return strategy.state_from_gmm(gmm0, dtype=backend.data.dtype)


def fedem_sharded(mesh, key, data, mask, k: int, *,
                  participation: float = 1.0, local_epochs: int = 1,
                  cohort: str = "cyclic", cohort_seed: int = 0,
                  stragglers=None, init_centers=None,
                  config: FitConfig | None = None,
                  transform=None) -> FedEMResult:
    """Iterative federated EM (Tian et al.) over the mesh: DEM's psum
    pattern with the partial-participation / local-epochs knobs. Under
    ``participation < 1`` the driver samples a cohort per round
    (``cohort``: "cyclic" or seeded "uniform") and each shard computes
    ONLY the cohort members it owns — per-shard round cost is O(m), not
    O(clients/shard). The result carries the populated communication
    ledger (cohort-sized uplink per round, init traffic included).
    ``init_centers`` overrides the scheme init from ``config.init``
    (which resolves exactly as in single-process FedEM: "auto" ->
    one-shot fed-kmeans)."""
    cfg = config if config is not None else FitConfig()
    backend = make_backend((data, mask), mesh)
    strategy = FedEMStrategy(
        k=k, covariance_type=cfg.covariance_type, backend=cfg.backend,
        chunk=cfg.resolve_chunk(source=False),
        init=_resolve_init(cfg.init, sources=False), host=False,
        tol=cfg.resolve_tol("em"), reg_covar=cfg.reg_covar,
        participation=float(participation), local_epochs=int(local_epochs),
        n_clients=backend.num_clients)
    sampler = None
    if strategy.participation < 1.0:
        sampler = make_sampler(cohort, backend.num_clients,
                               strategy.cohort_size(), seed=cohort_seed)
    return run_rounds(strategy, (backend.data, backend.mask), key=key,
                      mesh=mesh,
                      state0=_state_around(strategy, backend, init_centers,
                                           cfg),
                      max_rounds=cfg.resolve_max_iter("em"),
                      sampler=sampler, stragglers=stragglers,
                      transform=transform)


def fed_kmeans_sharded(mesh, key, data, mask, k: int, *,
                       config: FitConfig | None = None,
                       transform=None) -> FedKMeansResult:
    """Iterative federated k-means (Garst et al.) over the mesh: one psum
    of per-center label statistics (counts, sums, inertia) per round —
    the same collective as DEM with responsibilities replaced by hard
    labels."""
    cfg = config if config is not None else FitConfig()
    data, mask = jnp.asarray(data), jnp.asarray(mask)
    strategy = FedKMeansStrategy(
        k=k, assign_backend=resolve_backend(cfg.backend),
        chunk=cfg.resolve_chunk(source=False),
        init=_resolve_fedkmeans_init(cfg.init), host=False,
        tol=cfg.resolve_tol("kmeans"))
    return run_rounds(strategy, (data, mask), key=key, mesh=mesh,
                      max_rounds=cfg.resolve_max_iter("kmeans"),
                      transform=transform)
