"""Faults planted under the timed path, to show that the check sees them.

Each function takes a ``patch(owner, name, value)`` callable (pytest's
``monkeypatch.setattr``, or :func:`setattr` in a process of its own) and
breaks one thing a cell's answers depend on. Compiled programs that were
traced before the patch keep the old code: call ``jax.clear_caches()``
first. The benchmark's own runs plant none of them.
"""
from __future__ import annotations

import importlib

import jax.numpy as jnp


def _altered(gmm):
    from repro.core.gmm import GMM
    return GMM(gmm.weights, gmm.means.at[0].add(0.1), gmm.covs)


def fit_state_unchanged(patch, strategy: str) -> None:
    """An EM step, or a DEM round, returns the model it was given."""
    if strategy == "dem":
        from repro.core.dem import DEMStrategy
        real = DEMStrategy.server_combine
        patch(DEMStrategy, "server_combine",
              lambda self, state, stats: real(self, state, stats)
              ._replace(gmm=state.gmm))
    else:
        import repro.core.em as em
        real = em.em_step
        patch(em, "em_step", lambda gmm, *a, **k: (gmm, real(gmm, *a, **k)[1]))


def fit_half_rows(patch, strategy: str) -> None:
    """Every E-step leaves out the second half of its rows; the M-step
    then averages over the rest."""
    mod = importlib.import_module("repro.core.dem" if strategy == "dem"
                                  else "repro.core.em")
    real = mod.e_step_stats

    def e_step_stats(gmm, x, sample_weight=None, *a, **k):
        n = x.shape[0]
        w = jnp.ones(n, x.dtype) if sample_weight is None else sample_weight
        return real(gmm, x, w * (jnp.arange(n) < n // 2), *a, **k)
    patch(mod, "e_step_stats", e_step_stats)


def fit_answer_altered(patch, strategy: str) -> None:
    """The facade's returned global model has one mean moved by 0.1 (about
    one standard deviation of a feature)."""
    import repro.api as api
    cls = api.DEM if strategy == "dem" else api.FedGenGMM
    real = cls.run

    def run(self, clients, **kw):
        res = real(self, clients, **kw)
        return res._replace(global_gmm=_altered(res.global_gmm))
    patch(cls, "run", run)


def _wrap_merge(patch, change) -> None:
    import repro.core.fedgen as fedgen
    real = fedgen.merge_gmms
    patch(fedgen, "merge_gmms", lambda gmms, sizes: real(*change(gmms, sizes)))


def merge_unweighted(patch, strategy=None) -> None:
    """FedGenGMM's server merge leaves out the clients' sizes: every
    client's components weigh as if all clients held as many rows."""
    _wrap_merge(patch, lambda gmms, sizes: (gmms, sizes * 0 + 1))


def merge_shuffled(patch, strategy=None) -> None:
    """FedGenGMM's server merge pairs each client's weights with the next
    client's means and variances."""
    from repro.core.gmm import GMM
    _wrap_merge(patch, lambda gmms, sizes: (
        [GMM(g.weights, h.means, h.covs)
         for g, h in zip(gmms, gmms[1:] + gmms[:1])], sizes))


def _wrap_scores(patch, change) -> None:
    import repro.serve.engine as engine
    real = engine._score_slab

    def faulty(gmm, slab, mask, *, mode, backend):
        return change(real(gmm, slab, mask, mode=mode, backend=backend))
    patch(engine, "_score_slab", faulty)


def serve_answer_altered(patch, strategy=None) -> None:
    """One slot's scores come back 0.05 nats off."""
    _wrap_scores(patch, lambda s: s.at[0].add(0.05))


def serve_half_batch(patch, strategy=None) -> None:
    """The second half of the slots comes back unscored (zeros)."""
    _wrap_scores(patch, lambda s: s.at[s.shape[0] // 2:].set(0.0))


FIT = {"unchanged": fit_state_unchanged, "half": fit_half_rows,
       "altered": fit_answer_altered}
# faults of FedGenGMM's merge, under its synthetic set
MERGE = {"unweighted": merge_unweighted, "shuffled": merge_shuffled}
SERVE = {"altered": serve_answer_altered, "half": serve_half_batch}
