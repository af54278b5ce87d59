"""FedGenGMM (Algorithm 4.1): one-shot federated GMM learning.

Pipeline:
  1. local EM per client (vmap'd over padded client datasets, or a python
     loop with per-client BIC selection when K_c is heterogeneous),
  2. single communication round: clients ship (r, mu, Sigma, |D_c|),
  3. server merge: re-weight by |D_c|/|D|, concatenate, normalize,
  4. server samples |S| = H * sum_c K_c synthetic points from the merged
     mixture and trains the global GMM on S.

Clients arrive either as a padded :class:`ClientSplit` (resident arrays +
masks) or as a list of per-client :class:`DataSource` streams (out-of-core,
DESIGN.md §7); :func:`fedgengmm_cfg` dispatches on that input type with one
validated :class:`FitConfig`, and is what ``repro.api.FedGenGMM`` runs.

The sharded (shard_map) variant lives in ``repro.distributed.fed``; this
module is its single-process semantics and is what the paper benchmarks use.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.config import FitConfig, is_source_list
from repro.core.em import (EMResult, computed_lanes, fit_gmm_bic_cfg,
                           fit_gmm_cfg, fit_prepared_bytes, slab_row_block)
from repro.core.gmm import GMM, merge_gmms
from repro.core.partition import ClientSplit
from repro.data.sources import DataSource, SyntheticGMMSource
# CommStats / payload_floats historically lived here; the one copy of the
# communication accounting is now the federation ledger (DESIGN.md §9) and
# these re-exports keep the long-standing import path working.
from repro.fed.ledger import (CommStats, RoundPayload, dtype_itemsize,
                              payload_floats)
from repro.fed.runtime import run_rounds, slab_counters


class FedGenResult(NamedTuple):
    global_gmm: GMM
    local_gmms: list[GMM]
    synthetic: jax.Array       # the server-side dataset S: an (|S|, d)
    #                            array, or a SyntheticGMMSource when the
    #                            refit ran out-of-core (synthetic="source")
    comm: CommStats
    local_results: list[EMResult]
    global_result: Optional[EMResult] = None  # the server refit on S


# ----------------------------------------------------------------------
# Local training
# ----------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "config"))
def _train_locals_jit(key: jax.Array, data: jax.Array, mask: jax.Array,
                      k: int, config: FitConfig):
    c = data.shape[0]
    keys = jax.random.split(key, c)

    def one(key, x, w):
        res = fit_gmm_cfg(key, x, k, config, sample_weight=w)
        return res.gmm, res.log_likelihood, res.n_iter

    return jax.vmap(one)(keys, data, mask)


def train_locals_cfg(key: jax.Array, data: jax.Array, mask: jax.Array,
                     k: int, config: FitConfig) -> tuple[GMM, jax.Array,
                                                         jax.Array]:
    """vmap'd local EM, fixed K_c = k for all clients — the cfg-core behind
    :func:`train_locals` (the frozen :class:`FitConfig` rides through jit
    as a static argument, so the whole knob set is one hashable value).
    ``config.seed`` and ``config.init`` only feed the facade's key
    derivation / init-strategy naming and never the traced computation
    (local fits always use the k-means init), so both are normalized out
    of the static cache key — sweeping them must not recompile identical
    graphs.

    data: (C, N, d) padded, mask: (C, N). Returns stacked GMM with leaves
    of leading dim C, plus (C,) final logliks and iteration counts.
    tol/max_iter are normalized to their resolved EM values for the same
    reason seed/init are normalized out: a ``tol="auto"`` config and its
    concrete legacy twin describe the identical graph and must share one
    cache entry.
    """
    return _train_locals_jit(key, data, mask, k,
                             config.resolved_for("em").replace(seed=0,
                                                               init="auto"))


def train_locals(key: jax.Array, data: jax.Array, mask: jax.Array, k: int,
                 max_iter: int = 200, tol: float = 1e-3,
                 reg_covar: float = 1e-6,
                 covariance_type: str = "diag",
                 estep_backend: str = "auto",
                 chunk_size: Optional[int] = None) -> tuple[GMM, jax.Array,
                                                            jax.Array]:
    """Legacy keyword surface of :func:`train_locals_cfg` (internal)."""
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter)
    return train_locals_cfg(key, data, mask, k, cfg)


def train_locals_bic_cfg(key: jax.Array, split: ClientSplit,
                         k_candidates: Sequence[int],
                         config: FitConfig) -> list[EMResult]:
    """Per-client TrainGMM with BIC selection — heterogeneous K_c."""
    results = []
    for i in range(split.data.shape[0]):
        n = int(split.sizes[i])
        x = jnp.asarray(split.data[i, :n])
        res, _ = fit_gmm_bic_cfg(jax.random.fold_in(key, i), x, k_candidates,
                                 config)
        results.append(res)
    return results


def train_locals_bic(key: jax.Array, split: ClientSplit,
                     k_candidates: Sequence[int],
                     max_iter: int = 200, tol: float = 1e-3,
                     reg_covar: float = 1e-6,
                     covariance_type: str = "diag",
                     estep_backend: str = "auto",
                     chunk_size: Optional[int] = None) -> list[EMResult]:
    """Legacy keyword surface of :func:`train_locals_bic_cfg` (internal)."""
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter)
    return train_locals_bic_cfg(key, split, k_candidates, cfg)


def train_locals_sources_cfg(key: jax.Array,
                             sources: Sequence[DataSource],
                             config: FitConfig,
                             k: Optional[int] = None,
                             k_candidates: Optional[Sequence[int]] = None
                             ) -> list[EMResult]:
    """Local TrainGMM per client, each over its own :class:`DataSource` —
    the edge-device regime the paper targets: a client's dataset never has
    to fit in memory, only one block at a time. Fixed ``k`` or per-client
    BIC selection over ``k_candidates``. Sources are ragged by nature, so
    no padding, masks or sample weights appear anywhere on this path.
    """
    results = []
    for i, src in enumerate(sources):
        sub = jax.random.fold_in(key, i)
        if k is not None:
            res = fit_gmm_cfg(sub, src, k, config)
        else:
            assert k_candidates is not None, "need k or k_candidates"
            res, _ = fit_gmm_bic_cfg(sub, src, k_candidates, config)
        results.append(res)
    return results


def train_locals_from_sources(key: jax.Array,
                              sources: Sequence[DataSource],
                              k: Optional[int] = None,
                              k_candidates: Optional[Sequence[int]] = None,
                              max_iter: int = 200, tol: float = 1e-3,
                              reg_covar: float = 1e-6,
                              covariance_type: str = "diag",
                              estep_backend: str = "auto",
                              chunk_size: Optional[int] = None
                              ) -> list[EMResult]:
    """Deprecated: the per-client out-of-core local fits are the source arm
    of :func:`train_locals_sources_cfg`, which ``repro.api.FedGenGMM``
    drives. This shim forwards (bit-identical results) and will be
    removed."""
    warnings.warn(
        "train_locals_from_sources is deprecated; use "
        "repro.api.FedGenGMM(...).run(sources) for the full pipeline or "
        "train_locals_sources_cfg with a FitConfig — same engine, same bits",
        DeprecationWarning, stacklevel=2)
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter)
    return train_locals_sources_cfg(key, sources, cfg, k=k,
                                    k_candidates=k_candidates)


# ----------------------------------------------------------------------
# Server-side aggregation
# ----------------------------------------------------------------------

def aggregate_cfg(key: jax.Array, local_gmms: list[GMM], sizes,
                  config: FitConfig, h: int = 100,
                  k_global: Optional[int] = None,
                  k_candidates: Optional[Sequence[int]] = None,
                  synthetic: str = "resident") -> tuple[EMResult, jax.Array]:
    """Algorithm 4.1 lines 21-31: merge, sample S, train global model.

    The synthetic set S = H * sum_c K_c points is the largest dataset in
    the pipeline, so an integer ``config.chunk_size`` matters most here:
    it bounds the whole refit — the k-means init's Lloyd sweeps and label
    statistics, every E-step, and (on the ``k_candidates`` path) the
    per-candidate BIC scoring — at an O(chunk·K) working set (DESIGN.md
    §6).

    ``synthetic="source"`` goes one step further: S is never materialized
    at all. The refit consumes a :class:`SyntheticGMMSource` that
    regenerates seeded blocks on every pass (DESIGN.md §7), so the server's
    peak memory is independent of H and of the number of clients — the
    replay set can be arbitrarily large. Returned ``synthetic`` is then the
    source object instead of an array.
    """
    if synthetic not in ("resident", "source"):
        raise ValueError(f"synthetic must be 'resident' or 'source', "
                         f"got {synthetic!r}")
    n_synth = h * sum(g.n_components for g in local_gmms)
    with TraceAnnotation("repro.fedgen.merge_sample", rows=n_synth):
        merged = merge_gmms(local_gmms, jnp.asarray(sizes))
        k_sample, k_fit = jax.random.split(key)
        if synthetic == "source":
            synthetic = SyntheticGMMSource(merged, n_synth, k_sample)
        else:
            synthetic = merged.sample(k_sample, n_synth)
    with TraceAnnotation("repro.fedgen.refit"):
        if k_global is not None:
            res = fit_gmm_cfg(k_fit, synthetic, k_global, config)
        else:
            assert k_candidates is not None, "need k_global or k_candidates"
            res, _ = fit_gmm_bic_cfg(k_fit, synthetic, k_candidates, config)
    return res, synthetic


def aggregate(key: jax.Array, local_gmms: list[GMM], sizes,
              h: int = 100,
              k_global: Optional[int] = None,
              k_candidates: Optional[Sequence[int]] = None,
              max_iter: int = 200, tol: float = 1e-3,
              reg_covar: float = 1e-6,
              covariance_type: str = "diag",
              estep_backend: str = "auto",
              chunk_size: Optional[int] = None,
              synthetic: str = "resident") -> tuple[EMResult, jax.Array]:
    """Legacy keyword surface of :func:`aggregate_cfg` (internal)."""
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter)
    return aggregate_cfg(key, local_gmms, sizes, cfg, h=h, k_global=k_global,
                         k_candidates=k_candidates, synthetic=synthetic)


# ----------------------------------------------------------------------
# End-to-end FedGenGMM: the one-shot strategy on the federation runtime
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedGenStrategy:
    """Algorithm 4.1 as a one-shot :class:`~repro.fed.runtime.
    FederationStrategy`: the single round runs host-side (``run_once``) —
    local TrainGMM per client (vmap'd for a padded split, streamed for
    source clients, Python-level when per-client BIC selection makes K_c
    heterogeneous), then the server-side merge -> sample -> refit
    (:func:`aggregate_cfg`). The runtime contributes what every strategy
    shares: input-type dispatch and the communication ledger — uplink is
    each client's (K, 2d+1) parameter block + |D_c|, downlink the global
    broadcast, ``rounds=1`` by construction."""

    config: FitConfig
    k_clients: Optional[int] = None
    k_global: Optional[int] = None
    k_candidates: Optional[tuple] = None
    h: int = 100
    synthetic: str = "resident"

    one_shot = True
    name = "fedgen"

    def init_state(self, key: jax.Array, backend) -> dict:
        k_local_train, k_agg = jax.random.split(key)
        return {"k_local": k_local_train, "k_agg": k_agg}

    def run_once(self, state: dict, backend, transform=None, tparams=None,
                 tkey=None) -> dict:
        """The single communication round. With an uplink ``transform``
        installed (``run_rounds(transform=...)``, §11) every client's
        parameter-block payload ``(gmm, n_c)`` is transformed before the
        server sees it — for :class:`~repro.fed.transforms.GaussianDP`
        that is the paper-§4.4 one-shot DP release, the whole budget
        spent in this one round."""
        if backend.kind not in ("sources", "split"):
            raise TypeError(
                "FedGenStrategy runs ClientSplit or source-list clients; "
                "the mesh variant is repro.distributed.fedgen_sharded")
        d = int(backend.dim)
        lanes = computed_lanes(d, self.config.resolved_estep())
        sizes = backend.sizes
        if backend.kind == "split" and self.k_clients is not None:
            each = fit_prepared_bytes(int(backend.data.shape[1]), d,
                                      self.config)
            counters = slab_counters(
                backend, lanes, prepared_bytes=None if each is None
                else backend.num_clients * each,
                row_block=slab_row_block(
                    self.config.resolved_estep(),
                    self.config.resolve_chunk(source=False)))
            with TraceAnnotation("repro.fedgen.local", **counters):
                stacked, lls, iters = train_locals_cfg(
                    state["k_local"], backend.data, backend.mask,
                    self.k_clients, self.config)
            with TraceAnnotation("repro.fedgen.unstack"):
                local_gmms = [
                    GMM(stacked.weights[i], stacked.means[i], stacked.covs[i])
                    for i in range(backend.num_clients)]
                local_results = [
                    EMResult(g, lls[i], iters[i], jnp.array(True))
                    for i, g in enumerate(local_gmms)]
        else:
            # each client is fitted on its own rows, off the padded slab
            counters = slab_counters(backend, lanes)
            counters.pop("rows_computed", None)
            with TraceAnnotation("repro.fedgen.local", **counters):
                if backend.kind == "sources":
                    local_results = train_locals_sources_cfg(
                        state["k_local"], backend.sources, self.config,
                        k=self.k_clients, k_candidates=self.k_candidates)
                else:
                    assert self.k_candidates is not None, \
                        "need k_clients or k_candidates"
                    local_results = train_locals_bic_cfg(
                        state["k_local"], backend.split,
                        self.k_candidates, self.config)
            with TraceAnnotation("repro.fedgen.unstack"):
                local_gmms = [r.gmm for r in local_results]

        if transform is not None:
            # the uplink seam for the one-shot round: each client's
            # (gmm, n_c) block is transformed under the same shared
            # round key the iterative driver hands out (round 0); the
            # transform derives its per-client streams itself
            members = jnp.arange(len(local_gmms))
            rkey = jax.random.fold_in(tkey, 0)
            sizes_list = [float(n) for n in list(sizes)]
            released = []
            for i, (g, n) in enumerate(zip(local_gmms, sizes_list)):
                wire = transform.apply(rkey, tparams, (g, n), i, members)
                released.append(transform.finish(wire)[0])
            local_gmms = released

        res, synth = aggregate_cfg(
            state["k_agg"], local_gmms, sizes, self.config, h=self.h,
            k_global=self.k_global, k_candidates=self.k_candidates,
            synthetic=self.synthetic)
        return {"res": res, "synth": synth, "local_gmms": local_gmms,
                "local_results": local_results}

    def round_payload(self, backend, state) -> RoundPayload:
        local_gmms = state["local_gmms"]
        uplink = sum(payload_floats(g) + 1 for g in local_gmms)  # +1: |D_c|
        down = payload_floats(state["res"].gmm) * len(local_gmms)
        return RoundPayload(
            uplink_floats=uplink, downlink_floats=down,
            itemsize=dtype_itemsize(state["res"].gmm.means.dtype))

    def finalize(self, state, n_rounds, converged,
                 comm: CommStats) -> FedGenResult:
        return FedGenResult(state["res"].gmm, state["local_gmms"],
                            state["synth"], comm, state["local_results"],
                            state["res"])


def fedgengmm_cfg(key: jax.Array, clients, config: FitConfig,
                  k_clients: Optional[int] = None,
                  k_global: Optional[int] = None,
                  k_candidates: Optional[Sequence[int]] = None,
                  h: int = 100,
                  synthetic: str = "auto",
                  transform=None) -> FedGenResult:
    """Run the full one-shot pipeline — the cfg-core behind
    ``repro.api.FedGenGMM``, a thin wrapper building a
    :class:`FedGenStrategy` and handing it to the federation runtime
    (bit-identical to the pre-runtime pipeline; pinned in
    ``tests/test_fed_runtime.py``). Dispatch on the client input type:

    * a padded :class:`ClientSplit`: vmap'd local EM (fixed ``k_clients``)
      or per-client BIC selection (``k_candidates``), resident arrays;
    * a list/tuple of :class:`DataSource`: every client streams its local
      fit out-of-core, the single communication round ships only
      (K, 2d+1) parameter blocks, and (with ``synthetic="source"``) the
      server refit replays the merged mixture block-by-block — end to end,
      no stage holds O(N) rows.

    ``synthetic="auto"`` keeps the historical defaults per input type:
    a resident S for split clients, the seeded replay source for source
    clients.
    """
    sources = is_source_list(clients)
    if not sources and not isinstance(clients, ClientSplit):
        raise TypeError(
            f"fedgengmm clients must be a ClientSplit or a list of "
            f"DataSources, got {type(clients).__name__}")
    if synthetic == "auto":
        synthetic = "source" if sources else "resident"
    strategy = FedGenStrategy(
        config=config, k_clients=k_clients, k_global=k_global,
        k_candidates=None if k_candidates is None else tuple(k_candidates),
        h=h, synthetic=synthetic)
    return run_rounds(strategy, clients, key=key, max_rounds=1,
                      transform=transform)


def fedgengmm(key: jax.Array, split: ClientSplit,
              k_clients: Optional[int] = None,
              k_global: Optional[int] = None,
              k_candidates: Optional[Sequence[int]] = None,
              h: int = 100,
              max_iter: int = 200, tol: float = 1e-3,
              reg_covar: float = 1e-6,
              covariance_type: str = "diag",
              estep_backend: str = "auto",
              chunk_size: Optional[int] = None,
              synthetic: str = "resident") -> FedGenResult:
    """Legacy keyword surface of :func:`fedgengmm_cfg` (internal; prefer
    ``repro.api.FedGenGMM``). Either fix ``k_clients`` (paper's main
    experiments, K_c = K) or pass ``k_candidates`` for per-client BIC
    selection (heterogeneous models)."""
    cfg = FitConfig.from_legacy(
        backend=estep_backend, chunk_size=chunk_size,
        covariance_type=covariance_type, reg_covar=reg_covar, tol=tol,
        max_iter=max_iter)
    return fedgengmm_cfg(key, split, cfg, k_clients=k_clients,
                         k_global=k_global, k_candidates=k_candidates, h=h,
                         synthetic=synthetic)


def fedgengmm_from_sources(key: jax.Array,
                           sources: Sequence[DataSource],
                           k_clients: Optional[int] = None,
                           k_global: Optional[int] = None,
                           k_candidates: Optional[Sequence[int]] = None,
                           h: int = 100,
                           max_iter: int = 200, tol: float = 1e-3,
                           reg_covar: float = 1e-6,
                           covariance_type: str = "diag",
                           estep_backend: str = "auto",
                           chunk_size: Optional[int] = None,
                           synthetic: str = "source") -> FedGenResult:
    """Deprecated: ``repro.api.FedGenGMM(...).run(sources)`` dispatches on
    the input type, so the separate ``_from_sources`` spelling is obsolete.
    This shim forwards to the facade (bit-identical result) and will be
    removed."""
    warnings.warn(
        "fedgengmm_from_sources is deprecated; use "
        "repro.api.FedGenGMM(k_clients=..., k_global=...).run(sources) — "
        "same engine, same bits",
        DeprecationWarning, stacklevel=2)
    from repro.api import FedGenGMM  # facade sits above core; lazy
    fed = FedGenGMM(k_clients=k_clients, k_global=k_global,
                    k_candidates=k_candidates, h=h, synthetic=synthetic,
                    config=FitConfig.from_legacy(
                        backend=estep_backend, chunk_size=chunk_size,
                        covariance_type=covariance_type, reg_covar=reg_covar,
                        tol=tol, max_iter=max_iter))
    return fed.run(list(sources), key=key)
