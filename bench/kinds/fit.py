"""Federations back to back: the ``fit`` kind of traffic.

Set-up makes the configuration's client slab on the device and runs one
federation to warm up. The window then runs federations through the
facade a user calls (``repro.api.FedGenGMM.run`` or ``repro.api.DEM.run``,
by the mix's ``strategy``), each to its own stopping rule, until
``--seconds`` have passed; the fit under way then completes. ``fit_s`` is
their total time over their count.

The work is the same for every seed, in the seed's order. How long an EM
fit runs depends on its rows and its key (about 15 % from fit to fit on
one chip), so a window of fits drawn afresh per seed would measure the
draw. The mix's ``work_seed`` fixes the rows and a cycle of
``key_cycle`` fit keys; the run's seed orders the cycle and picks the
fits that are checked.

The check judges answers, each a fitted mixture, by the float64
reference on the rows it was fit to: how much one float64 EM step from
the returned model raises its average log-likelihood (``step_gain``: EM
stopped because its steps gained less than the tolerance, so a converged
fit reads below it; a model EM never brought to a fixed point on these
rows reads above), and how far the average log-likelihood the program
reported for its last step lies from the reference's for the returned
model (``ll_gap``: a sound fit differs by its last step's gain and
float32 rounding; a likelihood computed in lower precision, or over
other rows, differs by more).
FedGenGMM's local models are judged on their clients' rows, its global
model on the synthetic set its server refit was fit to; DEM's global
model on all rows. That synthetic set is itself held to the float64
merge of the returned local models, weighted by the clients' sizes that
the benchmark drew (``synthetic_misfit``: the largest mean squared
z-score of its components' counts, means and variances; at most about 1
for rows drawn from that merge, well above for rows drawn with other
weights, from other components or with other spreads). The checked fits
are the longest one and ``check_fits - 1`` others drawn from the seed.
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from lib import datasets, reference


def _runner(ctx):
    from repro.api import DEM, FedGenGMM, FitConfig
    k = int(ctx.cfg["k"])
    strategy = ctx.traffic["strategy"]
    config = FitConfig(**ctx.traffic["fit_config"])
    if strategy == "fedgen":
        return FedGenGMM(k_clients=k, k_global=k, h=int(ctx.traffic["h"]),
                         config=config)
    if strategy == "dem":
        return DEM(k, config=config)
    raise ValueError(f"unknown fit strategy {strategy!r}")


def fit_keys(work_seed: int, n: int, seed: int) -> list:
    """The cycle of ``n`` fit keys fixed by ``work_seed``, in the order
    ``seed`` draws."""
    base = datasets.run_key(work_seed)
    order = np.random.default_rng(seed).permutation(n)
    return [jax.random.fold_in(base, 1 + int(j)) for j in order]


def make_split(ctx, data_seed: int):
    from repro.core.partition import ClientSplit
    with ctx.spans.span("bench.generate"):
        data, mask, sizes, class_counts = datasets.client_slab(
            ctx.gen, ctx.cfg, data_seed,
            jax.random.fold_in(datasets.run_key(data_seed), 0))
        jax.block_until_ready((data, mask))
    return ClientSplit(data, mask, sizes, class_counts)


def setup(ctx) -> dict:
    work = int(ctx.traffic["work_seed"])
    split = make_split(ctx, work)
    runner = _runner(ctx)
    with ctx.spans.span("bench.warmup"):
        warm = runner.run(split, key=jax.random.fold_in(
            datasets.run_key(work), 0))
        jax.block_until_ready(warm.global_gmm)
    return {"split": split, "runner": runner,
            "keys": fit_keys(work, int(ctx.traffic["key_cycle"]), ctx.seed)}


def window(ctx, state, stop_trace) -> dict:
    runner, split, keys = state["runner"], state["split"], state["keys"]
    fits, results = [], []
    start = time.perf_counter()
    i = 0
    while time.perf_counter() - start < ctx.seconds:
        c0 = ctx.compile.seconds
        with ctx.spans.span("bench.fit"):
            t0 = time.perf_counter()
            res = runner.run(split, key=keys[i % len(keys)])
            jax.block_until_ready(res.global_gmm)
            t1 = time.perf_counter()
        fits.append({"t0": t0, "t1": t1,
                     "compile_s": ctx.compile.seconds - c0})
        results.append(res)
        i += 1
    stop_trace()
    sizes = np.asarray(split.sizes)
    for f, res in zip(fits, results):
        f["rounds"] = int(getattr(res, "n_rounds", 1))
        f["estep_rows"] = estep_rows(res, sizes)
    print("fit seconds: " + " ".join(f"{f['t1'] - f['t0']:.4f}" for f in fits),
          file=sys.stderr, flush=True)
    return {"fits": fits, "results": results}


def end_to_end(ctx, state, rec) -> dict:
    fits = rec["fits"]
    return {"fit_s": sum(f["t1"] - f["t0"] for f in fits) / len(fits)}


def counts(ctx, state, rec) -> tuple[int, int]:
    return len(rec["fits"]), 0


def estep_rows(res, sizes) -> int:
    """Rows of all the E-steps one federation reports, each row counted
    once per E-step over it: every client's iterations times its rows in
    FedGenGMM's local EM (a client that has converged counts no more,
    though a vmapped loop runs on until the last has); every client's rows
    in each DEM round. FedGenGMM's server refit reports no iteration
    count, so its E-steps are not counted."""
    if hasattr(res, "local_results"):
        iters = np.array([int(r.n_iter) for r in res.local_results])
        return int(np.sum(iters * sizes))
    return int(res.n_rounds) * int(np.sum(sizes))


def layer_context(ctx, state, rec) -> dict:
    fits = rec["fits"]
    return {"fits": fits, "d": int(ctx.cfg["d"]), "k": int(ctx.cfg["k"]),
            "estep_rows": sum(f["estep_rows"] for f in fits)}


def _picked(ctx, rec) -> list:
    fits = rec["fits"]
    longest = max(range(len(fits)), key=lambda i: fits[i]["t1"] - fits[i]["t0"])
    others = [i for i in range(len(fits)) if i != longest]
    rng = np.random.default_rng(ctx.seed)
    n = min(int(ctx.traffic["check_fits"]) - 1, len(others))
    return [longest] + sorted(rng.choice(others, n, replace=False).tolist())


def _host_params(gmm):
    return tuple(np.asarray(v, np.float64)
                 for v in (gmm.weights, gmm.means, gmm.covs))


def release(ctx, state, rec) -> None:
    """Bring what the check needs to the host and free the device."""
    split = state.pop("split")
    state["rows"] = [np.asarray(split.data[c, : int(n)])
                     for c, n in enumerate(split.sizes)]
    state["sizes"] = np.asarray(split.sizes)
    checked = {}
    for i in _picked(ctx, rec):
        res = rec["results"][i]
        entry = {"global": _host_params(res.global_gmm),
                 "ll": float(getattr(res, "log_likelihood", np.nan))}
        if hasattr(res, "local_gmms"):
            entry["local"] = [_host_params(g) for g in res.local_gmms]
            entry["local_ll"] = [float(r.log_likelihood)
                                 for r in res.local_results]
            entry["synthetic"] = np.asarray(res.synthetic)
        checked[i] = entry
    state["checked"] = checked
    rec["results"] = None
    state.pop("runner")


def numbers(checked: dict, rows: list, sizes,
            reg_covar: float = 1e-6) -> dict:
    """The compared numbers over the checked fits (see the module doc)."""
    out = {"local_step_gain": -np.inf, "global_step_gain": -np.inf,
           "synthetic_misfit": -np.inf, "ll_gap": 0.0}
    for entry in checked.values():
        if "local" in entry:
            for x, params, ll in zip(rows, entry["local"], entry["local_ll"]):
                gain, ll64 = reference.step_gain(x, *params, reg_covar)
                out["local_step_gain"] = max(out["local_step_gain"], gain)
                out["ll_gap"] = max(out["ll_gap"], abs(ll - ll64))
            merged = reference.merged_mixture(entry["local"], sizes)
            out["synthetic_misfit"] = max(
                out["synthetic_misfit"],
                reference.sample_misfit(entry["synthetic"], *merged))
            gain, _ = reference.step_gain(entry["synthetic"],
                                          *entry["global"], reg_covar)
        else:
            gain, ll64 = reference.step_gain(np.concatenate(rows),
                                             *entry["global"], reg_covar)
            out["ll_gap"] = max(out["ll_gap"], abs(entry["ll"] - ll64))
        out["global_step_gain"] = max(out["global_step_gain"], gain)
    if not any("local" in e for e in checked.values()):
        del out["local_step_gain"], out["synthetic_misfit"]
    return out


def check(ctx, state, rec) -> list:
    got = numbers(state["checked"], state["rows"], state["sizes"])
    return [{"name": name, "value": float(value),
             "limit": float(ctx.limits[name])}
            for name, value in got.items()]
