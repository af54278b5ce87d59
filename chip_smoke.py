#!/usr/bin/env python
"""Smoke test of the federated GMM system on a TPU.

Drives the main path once through the entry points a user calls, at the
widest paper geometry the repository generates: WADI (d = 84, K = 10,
20 clients, quantity partitioning), with 1,209,600 training rows, the
public training set's scale (14 days of normal operation at 1 Hz), all
generated from ``--seed``. In one process, in order:

1. report the device; exit non-zero unless JAX finds a TPU;
2. one-shot FedGenGMM (``repro.api.FedGenGMM``) and a centralized
   ``GMMEstimator`` fit on the same rows;
3. a few rounds of iterative DEM (``repro.api.DEM``);
4. anomaly scoring of a few dozen mixed-size requests of held-out and
   attack rows through ``repro.serve.ScoringEngine``, drained to empty;
5. the fused E-step statistics of every client and the served scores
   against a float64 numpy reference that shares no code with ``repro``;
6. proof that the Pallas kernels ran: ``"auto"`` resolved to
   ``"fused"``, and the compiled E-step and scoring programs hold a
   ``tpu_custom_call``.

With ``--chips 4`` it runs only the sharded path instead:
``repro.api.DEM(k, mesh=...)`` and ``fedgen_sharded`` on a 4-device mesh
(5 clients per chip) beside the same strategies on unsharded clients.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Any failed check exits non-zero without it.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --chips 4       # the sharded path, four chips
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

WADI_TRAIN_ROWS = 14 * 24 * 3600     # 1,209,600: 14 days at 1 Hz
QUANTITY_ALPHA = 2                   # classes per client (paper: 1, 2, 3)
DEM_ROUNDS = 5
N_REQUESTS = 48
CHECK_ROWS_PER_CLIENT = 4096

U32 = 2.0 ** -24                     # unit roundoff of float32
LOG_2PI = float(np.log(2.0 * np.pi))


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


# ----------------------------------------------------------------------
# The float64 numpy reference (independent of repro)
# ----------------------------------------------------------------------

def _params64(gmm):
    return (np.asarray(gmm.weights, np.float64),
            np.asarray(gmm.means, np.float64),
            np.asarray(gmm.covs, np.float64))


def ref_weighted_logpdf(x, gmm):
    """(N, d) -> (N, K): log w_k + log N(x | mu_k, diag(var_k)) in float64
    by the direct (x - mu)^2 / var form (the code under test uses the
    matmul identity instead)."""
    w, mu, var = _params64(gmm)
    x = np.asarray(x, np.float64)
    out = np.empty((x.shape[0], mu.shape[0]))
    for j in range(mu.shape[0]):
        diff = x - mu[j]
        out[:, j] = (-0.5 * (np.sum(diff * diff / var[j], axis=1)
                             + np.sum(np.log(var[j]))
                             + x.shape[1] * LOG_2PI) + np.log(w[j]))
    return out


def ref_logsumexp(lp):
    m = lp.max(axis=1, keepdims=True)
    return m[:, 0] + np.log(np.exp(lp - m).sum(axis=1))


def identity_error_bound(x, gmm):
    """Per-row bound on the float32 rounding error of a mixture component
    log density computed by the matmul identity
    ``x^2 @ (-1/(2 var)) + x @ (mu/var) + c``.

    An inner product of n float32 terms is off by at most
    ``gamma_n * sum|terms|``, ``gamma_n = n u / (1 - n u)`` (Higham,
    Accuracy and Stability of Numerical Algorithms, 2nd ed., eq. 3.5).
    The identity sums 2d products plus a constant that is itself a sum
    of d terms; n = 2d + 8 also covers the roundings of x^2, 1/var and
    mu/var. A matmul that rounds its inputs to bfloat16 (8-bit
    mantissa) errs by about ``2^-9 sum|terms| / sqrt(d)``, some 50 times
    this bound at d = 84, so the check separates the two."""
    w, mu, var = _params64(gmm)
    x = np.asarray(x, np.float64)
    d = x.shape[1]
    mag = ((x * x) @ (0.5 / var).T + np.abs(x) @ np.abs(mu / var).T
           + (0.5 * np.sum(mu * mu / var + np.abs(np.log(var)), axis=1)
              + 0.5 * d * LOG_2PI + np.abs(np.log(w)))[None, :])
    n = 2 * d + 8
    return n * U32 / (1 - n * U32) * mag.max(axis=1)


def score_tolerance(x, gmm, ref):
    """Per-row tolerance on a mixture log density: the identity bound
    plus 16 ulps of the result for the logsumexp's exp, sum and log."""
    return identity_error_bound(x, gmm) + 16 * U32 * np.maximum(
        1.0, np.abs(ref))


def estep_error_ratio(x, wts, gmm, got) -> float:
    """Compare one client's fused E-step ``(s0, s1, s2, loglik)`` with
    float64. A log-density error of at most B moves each responsibility
    by at most a factor e^(2B) - 1 ~ 2B; float32 accumulation over N rows
    adds gamma_N of the summed magnitude. Those give per-entry
    tolerances; rows underflowing float32 exp (below 1e-30 in total)
    get an absolute floor. Returns the worst error over tolerance (a
    match is at most 1)."""
    x64 = np.asarray(x, np.float64)
    wts = np.asarray(wts, np.float64)
    lp = ref_weighted_logpdf(x64, gmm)
    ln = ref_logsumexp(lp)
    rw = np.exp(lp - ln[:, None]) * wts[:, None]
    bound = identity_error_bound(x64, gmm)
    n = x64.shape[0]
    gamma_n = n * U32 / (1 - n * U32)
    rel = rw * (2 * bound + gamma_n)[:, None]
    floor = 1e-30 * n
    ref = {"s0": rw.sum(axis=0), "s1": rw.T @ x64, "s2": rw.T @ (x64 * x64),
           "loglik": np.sum(ln * wts)}
    tol = {"s0": rel.sum(axis=0) + floor,
           "s1": rel.T @ np.abs(x64) + floor,
           "s2": rel.T @ (x64 * x64) + floor,
           "loglik": np.sum(wts * (bound + 16 * U32
                                   * np.maximum(1, np.abs(ln))))
           + gamma_n * np.sum(wts * np.abs(ln))}
    return max(float(np.max(np.abs(np.asarray(getattr(got, name),
                                              np.float64) - ref[name])
                            / tol[name]))
               for name in ref)


# ----------------------------------------------------------------------
# Data
# ----------------------------------------------------------------------

def make_wadi(rows: int, seed: int):
    """WADI-like rows at ``rows`` training rows (test rows at the public
    test set's ratio: 2 days per 14), partitioned over the dataset's 20
    clients by its quantity scheme."""
    from repro.core.partition import partition
    from repro.data.datasets import wadi_like
    rng = np.random.default_rng(seed)
    ds = wadi_like(rng, n_train=rows, n_test=max(rows // 7, 64))
    split = partition(rng, ds.x_train, ds.y_train, ds.n_clients, ds.scheme,
                      QUANTITY_ALPHA)
    return ds, split


def held_out_ll(gmm, x) -> float:
    import repro.api as api
    return float(api.score(gmm, np.asarray(x, np.float32)))


# ----------------------------------------------------------------------
# One chip: the main path
# ----------------------------------------------------------------------

def run_main(args) -> None:
    import jax
    import jax.numpy as jnp
    from repro.api import DEM, FedGenGMM, FitConfig, GMMEstimator
    from repro.core.em import e_step_stats
    from repro.core.metrics import auc_pr
    from repro.serve import ScoreConfig, ScoreRequest, ScoringEngine
    from repro.serve.engine import _score_slab

    ds, split = make_wadi(args.rows, args.seed)
    k = ds.k_global
    x_test = ds.x_test_in.astype(np.float32)
    x_ood = ds.x_test_ood.astype(np.float32)
    print(f"data: {ds.x_train.shape[0]} train rows, d={ds.x_train.shape[1]}, "
          f"K={k}, {split.data.shape[0]} clients, slab {split.data.shape}, "
          f"{x_test.shape[0]} held-out rows, {x_ood.shape[0]} attack rows",
          flush=True)

    cfg = FitConfig(seed=args.seed)
    check(cfg.resolved_estep() == "fused",
          "FitConfig backend 'auto' resolves to the fused E-step kernel")

    fed = FedGenGMM(k_clients=k, k_global=k, config=cfg)
    fed_res = fed.run(split)
    jax.block_until_ready(fed_res.global_gmm)
    central = GMMEstimator(k, config=cfg).fit(
        ds.x_train.astype(np.float32))
    jax.block_until_ready(central.gmm_)
    dem = DEM(k, config=cfg.replace(max_iter=DEM_ROUNDS))
    dem_res = dem.run(split)
    jax.block_until_ready(dem_res.global_gmm)
    lls = {"fedgen": held_out_ll(fed.global_gmm_, x_test),
           "centralized": held_out_ll(central.gmm_, x_test),
           "dem": held_out_ll(dem.global_gmm_, x_test)}
    print(f"held-out avg log-likelihood: fedgen {lls['fedgen']:.4f}, "
          f"centralized {lls['centralized']:.4f}, dem "
          f"{lls['dem']:.4f} after {int(dem_res.n_rounds)} rounds", flush=True)
    check(all(np.isfinite(v) for v in lls.values()),
          "held-out log-likelihoods are finite")

    # -- serving ------------------------------------------------------------
    gmm = fed.global_gmm_
    rng = np.random.default_rng(args.seed + 1)
    pool = np.concatenate([x_test, x_ood])
    is_ood = np.concatenate([np.zeros(len(x_test), bool),
                             np.ones(len(x_ood), bool)])
    sizes = np.exp(rng.uniform(np.log(16), np.log(3000),
                               N_REQUESTS)).astype(int)
    # every fourth row an attack row, the rest held-out normal operation
    picks = [np.where(rng.random(n) < 0.25,
                      rng.integers(len(x_test), len(pool), n),
                      rng.integers(0, len(x_test), n)) for n in sizes]
    requests = [ScoreRequest(i, pool[p]) for i, p in enumerate(picks)]
    engine = ScoringEngine(gmm, ScoreConfig(mode="anomaly", slots=8,
                                            rows_per_slot=512))
    for r in requests:
        engine.submit(r)
    results, steps = [], 0
    while engine.pending_requests and steps < 10 * N_REQUESTS:
        results.extend(engine.step())
        steps += 1
    print(f"serving: {len(requests)} requests, {int(sizes.sum())} rows, "
          f"{steps} micro-batches", flush=True)
    got = {r.rid: r for r in results}
    check(len(results) == len(requests) and sorted(got) == list(
        range(len(requests))), "every request retired exactly once")
    check(all(got[i].scores.shape == (n,) for i, n in enumerate(sizes)),
          "every result is row-aligned with its request")
    check(engine.backend == "fused",
          "the engine's 'auto' backend resolved to the fused kernel")

    # -- correctness against float64 numpy ------------------------------------
    served = np.concatenate([got[i].scores for i in range(len(requests))])
    rows = np.concatenate([r.rows for r in requests])
    ref = ref_logsumexp(ref_weighted_logpdf(rows, gmm))
    tol = score_tolerance(rows, gmm, ref)
    err = np.abs(-served.astype(np.float64) - ref)
    print(f"served scores vs float64: max |err| {err.max():.3e}, "
          f"max err/tol {np.max(err / tol):.3f}", flush=True)
    check(bool(np.all(err <= tol)),
          "served anomaly scores match the float64 log density")

    ref_api = np.asarray(jax.jit(lambda g, x: g.log_prob(x))(
        gmm, jnp.asarray(rows)), np.float64)
    err_api = np.abs(ref_api - ref)
    print(f"XLA reference log_prob vs float64: max |err| "
          f"{err_api.max():.3e}, max err/tol "
          f"{np.max(err_api / tol):.3f}", flush=True)
    check(bool(np.all(err_api <= tol)),
          "XLA reference-path log densities match the float64 density")

    xs = split.data[:, :CHECK_ROWS_PER_CLIENT]
    ws = split.mask[:, :CHECK_ROWS_PER_CLIENT]
    client_estep = jax.jit(jax.vmap(
        lambda g, x, w: e_step_stats(g, x, w, "auto"),
        in_axes=(None, 0, 0)))
    stats = jax.device_get(client_estep(gmm, xs, ws))
    ratios = [estep_error_ratio(xs[c], ws[c], gmm,
                                type(stats)(*(s[c] for s in stats)))
              for c in range(xs.shape[0])]
    print(f"fused E-step, {xs.shape[0]} clients x {xs.shape[1]} rows: "
          f"err/tol per client {np.round(ratios, 4).tolist()}",
          flush=True)
    check(max(ratios) <= 1.0,
          "every client's fused E-step statistics match float64")

    labels = np.concatenate([is_ood[p] for p in picks])
    print(f"anomaly AUC-PR of attack rows (served scores): "
          f"{auc_pr(served, labels):.4f} (attack share "
          f"{labels.mean():.3f})", flush=True)

    # -- the kernels really ran on the chip ---------------------------------
    estep_hlo = client_estep.lower(gmm, xs, ws).compile().as_text()
    check("tpu_custom_call" in estep_hlo,
          "the compiled vmapped E-step holds a Pallas tpu_custom_call")
    geometry = (engine.config.slots, engine.config.rows_per_slot)
    slab = jax.ShapeDtypeStruct(geometry + (gmm.n_features,),
                                jnp.float32)
    mask = jax.ShapeDtypeStruct(geometry, jnp.float32)
    score_hlo = _score_slab.lower(gmm, slab, mask, mode="anomaly",
                                  backend=engine.backend
                                  ).compile().as_text()
    check("tpu_custom_call" in score_hlo,
          "the compiled scoring step holds a Pallas tpu_custom_call")


# ----------------------------------------------------------------------
# Four chips: the sharded path and its unsharded comparison
# ----------------------------------------------------------------------

def _max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(a, b))


def run_sharded(args) -> None:
    import jax
    from repro.api import DEM, FedGenGMM, FitConfig
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import fedgen_sharded

    ds, split = make_wadi(args.rows, args.seed)
    k = ds.k_global
    x_test = ds.x_test_in.astype(np.float32)
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    # each chip receives only its clients' rows, straight from the host
    data, mask = jax.device_put((split.data, split.mask),
                                NamedSharding(mesh, P("data")))
    print(f"mesh {dict(mesh.shape)}: {split.data.shape[0]} clients, "
          f"{split.data.shape[0] // n_dev} per chip; slab sharding "
          f"{data.sharding}", flush=True)
    key = jax.random.key(args.seed)
    # tol = 0 runs every DEM round on both sides: a convergence test at
    # the tolerance's edge must not stop one side a round before the other
    cfg = FitConfig(seed=args.seed)
    dem_cfg = cfg.replace(max_iter=DEM_ROUNDS, tol=0.0)

    fed = FedGenGMM(k_clients=k, k_global=k, config=cfg).run(split,
                                                             key=key)
    jax.block_until_ready(fed.global_gmm)
    sharded = fedgen_sharded(mesh, key, data, mask, k, k, config=cfg)
    jax.block_until_ready(sharded.global_gmm)
    failures = []

    def soft_check(ok: bool, what: str) -> None:
        """Record a failed comparison and go on, so one run reports all."""
        try:
            check(ok, what)
        except SmokeFailure:
            failures.append(what)
            print(f"  FAILED: {what}", flush=True)

    per_client = [_max_abs_diff(
        (g.weights, g.means, g.covs),
        (sharded.local_weights[c], sharded.local_means[c],
         sharded.local_covs[c])) for c, g in enumerate(fed.local_gmms)]
    ll_fed, ll_sh = (held_out_ll(fed.global_gmm, x_test),
                     held_out_ll(sharded.global_gmm, x_test))
    print(f"fedgen: local params max |diff| per client "
          f"{[float(f'{v:.2e}') for v in per_client]}; held-out ll "
          f"unsharded {ll_fed:.6f} sharded {ll_sh:.6f}", flush=True)
    # Each client runs the same program on the same rows and key on both
    # sides, but the vmapped programs over 5 and over 20 clients may sum
    # in another order. A local EM that stops one iteration apart moves
    # parameters by far less than 1e-2 (features lie in [0, 1]). A client
    # can still land in another local optimum: k-means++ draws each seed
    # from 16,384 rows by a categorical argmax, and a near-tie there flips
    # on a last-bit difference. So three in four clients must match, and
    # the global model must agree to 0.1 nats per row, a tenth of the
    # FedGenGMM-to-centralized gap on this data (0.93 nats).
    matching = sum(v <= 1e-2 for v in per_client)
    soft_check(matching >= 0.75 * len(per_client),
               f"fedgen_sharded local models match unsharded "
               f"({matching} of {len(per_client)} clients)")
    soft_check(abs(ll_fed - ll_sh) <= 0.1,
               "fedgen_sharded global model matches unsharded held-out ll")

    dem = DEM(k, config=dem_cfg).run(split, key=key)
    jax.block_until_ready(dem.global_gmm)
    # the same facade on the mesh: its fed-kmeans init runs on the shards
    # with the single-process key schedule
    placed = split._replace(data=data, mask=mask)
    dem_sh = DEM(k, config=dem_cfg, mesh=mesh).run(placed, key=key)
    g_sh, rounds = dem_sh.global_gmm, dem_sh.n_rounds
    jax.block_until_ready(g_sh)
    g_un = dem.global_gmm
    print(f"dem: {int(dem.n_rounds)} / {int(rounds)} rounds; weights "
          f"max |diff| {_max_abs_diff([g_un.weights], [g_sh.weights]):.3e}, "
          f"means {_max_abs_diff([g_un.means], [g_sh.means]):.3e}, covs "
          f"{_max_abs_diff([g_un.covs], [g_sh.covs]):.3e}", flush=True)
    soft_check(int(dem.n_rounds) == int(rounds) == DEM_ROUNDS,
               "both DEM runs took every round")
    # The sides differ only in the order client statistics are summed
    # (a psum of 4 partial sums vs one sum of 20): ~C u = 1.2e-6 relative
    # per round, which five EM rounds do not grow past 1e-4.
    for f in ("weights", "means", "covs"):
        a = np.asarray(getattr(g_un, f))
        b = np.asarray(getattr(g_sh, f))
        soft_check(bool(np.allclose(a, b, rtol=1e-3, atol=1e-4)),
                   f"DEM on the mesh: {f} match unsharded DEM")
    if failures:
        raise SmokeFailure("; ".join(failures))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=WADI_TRAIN_ROWS,
                        help="training rows (default: WADI's 1,209,600)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the sharded path on 4 chips")
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this smoke runs on the chip "
              "only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        (run_sharded if args.chips == 4 else run_main)(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
