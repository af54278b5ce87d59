"""Open-loop anomaly scoring: the ``serve`` kind of traffic.

Set-up makes, on the device from the seed, the configuration's held-out
rows and attack rows at its anomaly share, shuffled together into one
pool, and the served model: each class's share, mean and variance over
freshly made training rows (no federation is fitted). It builds
``repro.serve.ScoringEngine`` with the configuration's slot geometry and
warms up its one scoring shape.

The mix fixes a rate. Requests arrive open-loop over the window: the
gaps between arrivals are the quantiles of an exponential at that rate
and the sizes the quantiles of a log-uniform over ``[size_min,
size_max]`` rows, both in an order drawn from the seed, so every seed
offers the same work. Each request is a run of consecutive pool rows
from an offset drawn from the seed. Latency runs from a request's due
time to the step that retired it; a traced run traces the window's first
``trace_seconds``; after the window closes the engine
drains for at most ``drain_s`` more seconds, and a request still
unanswered then has failed. Every turn of the window's loop that takes
longer than ``STALL_S`` is recorded, with what the loop did in it, and
the longest are printed after the window.

The check compares served scores (anomaly score = -log p(x)) of the
longest retired request and others drawn from the seed, up to
``check_rows`` rows, with the float64 reference density, as a share of
each row's float32 error bound.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import datasets, reference
from lib.control import matmul_high

STALL_S = 0.02


def schedule(rate: float, seconds: float, size_min: int, size_max: int,
             seed: int):
    """(due times, sizes): the same multiset of gaps and sizes for every
    seed, in the seed's order."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / gaps.sum()
    lo, hi = np.log(size_min), np.log(size_max)
    sizes = rng.permutation(np.rint(np.exp(lo + q * (hi - lo)))).astype(int)
    return due, sizes


def setup(ctx) -> dict:
    from repro.core.gmm import GMM
    from repro.serve import ScoreConfig, ScoreRequest, ScoringEngine
    t = ctx.traffic
    with ctx.spans.span("bench.generate"):
        x, (w, mu, var) = datasets.scoring_pool(
            ctx.gen, ctx.cfg, jax.random.fold_in(ctx.key, 0))
        pool = np.asarray(x)
    due, sizes = schedule(float(t["rate_per_s"]), ctx.seconds,
                          int(t["size_min"]), int(t["size_max"]), ctx.seed)
    rng = np.random.default_rng([ctx.seed, 1])
    offsets = rng.integers(0, len(pool) - sizes + 1)
    requests = [ScoreRequest(i, pool[o:o + s])
                for i, (o, s) in enumerate(zip(offsets, sizes))]
    serve = ctx.cfg["serve"]
    engine = ScoringEngine(GMM(w, mu, var), ScoreConfig(
        mode=serve["mode"], slots=int(serve["slots"]),
        rows_per_slot=int(serve["rows_per_slot"])))
    with ctx.spans.span("bench.warmup"):
        warm = [ScoreRequest(-1 - i, pool[:n]) for i, n in
                enumerate((1, int(serve["rows_per_slot"]) + 1))]
        engine.run(warm)
    return {"engine": engine, "requests": requests, "due": due,
            "sizes": sizes, "pool": pool,
            "params": (np.asarray(w, np.float64), np.asarray(mu, np.float64),
                       np.asarray(var, np.float64))}


def window(ctx, state, stop_trace) -> dict:
    engine, requests, due = state["engine"], state["requests"], state["due"]
    n = len(requests)
    late = np.full(n, np.nan)
    retired = np.full(n, np.nan)
    scores = {}
    steps = []
    stalls = []
    span = ctx.spans.span
    drain_s = float(ctx.traffic["drain_s"])
    trace_s = min(float(ctx.traffic["trace_seconds"]), ctx.seconds)
    traced_s = None

    def step(t0):
        with span("bench.engine.step"):
            s0 = time.perf_counter()
            out = engine.step()
            s1 = time.perf_counter()
        steps.append((s0 - t0, s1 - t0, len(out)))
        for r in out:
            retired[r.rid] = s1 - t0
            scores[r.rid] = r.scores

    t0 = time.perf_counter()
    opened = time.time()
    i = 0
    last, did = 0.0, ["start"]
    while True:
        now = time.perf_counter() - t0
        if now - last > STALL_S:
            stalls.append((last, now - last, "+".join(did) or "idle"))
        last, did = now, []
        if i < n and due[i] <= now:
            did.append("submit")
            with span("bench.generator"):
                while i < n and due[i] <= now:
                    engine.submit(requests[i])
                    late[i] = now - due[i]
                    i += 1
        if traced_s is None and now >= trace_s:
            stop_trace()
            traced_s = now
            did.append("stop trace")
        if now >= ctx.seconds:
            break
        if engine.pending_requests:
            did.append("step")
            step(t0)
        elif i < n:
            wait = due[i] - (time.perf_counter() - t0)
            if wait > 1e-3:
                did.append(f"sleep {wait - 5e-4:.4f} s")
                time.sleep(wait - 5e-4)
        else:
            time.sleep(min(1e-3, ctx.seconds - now))
    stop_trace()
    while engine.pending_requests and \
            time.perf_counter() - t0 < ctx.seconds + drain_s:
        step(t0)
    return {"late": late, "retired": retired, "scores": scores,
            "steps": steps, "stalls": stalls, "submitted": i,
            "opened": opened,
            "traced_s": traced_s}


def end_to_end(ctx, state, rec) -> dict:
    due, retired = state["due"], rec["retired"]
    sub = rec["submitted"]
    lat = (retired[:sub] - due[:sub]) * 1e3
    lat = lat[np.isfinite(lat)]
    in_window = np.isfinite(retired) & (retired <= ctx.seconds)
    late = rec["late"][:sub] * 1e3
    print(f"generator lateness: p50 {np.percentile(late, 50):.4f} ms, "
          f"max {late.max():.4f} ms over {sub} requests", flush=True)
    print("latency: " + ", ".join(
        f"p{q} {np.percentile(lat, q):.4f} ms" for q in (50, 90, 95, 99))
        + f", mean {lat.mean():.4f} ms", flush=True)
    longest = sorted(rec["stalls"], key=lambda s: -s[1])[:5]
    print(f"window opened at {rec['opened']:.3f} (Unix time); "
          f"loop turns over {STALL_S * 1e3:.0f} ms: {len(rec['stalls'])}, "
          f"{sum(s[1] for s in rec['stalls']):.4f} s in all; "
          "longest: " + (", ".join(f"{d * 1e3:.1f} ms at {t:.3f} s ({what})"
                                   for t, d, what in longest) or "none"),
          flush=True)
    return {"score_p99_ms": float(np.percentile(lat, 99)),
            "scored_rows_per_s":
                float(state["sizes"][in_window].sum()) / ctx.seconds}


def counts(ctx, state, rec) -> tuple[int, int]:
    sub = rec["submitted"]
    return sub, int(np.sum(~np.isfinite(rec["retired"][:sub])))


def layer_context(ctx, state, rec) -> dict:
    return {"steps": rec["steps"], "retired": rec["retired"],
            "sizes": state["sizes"], "seconds": ctx.seconds,
            "traced_s": rec["traced_s"],
            "d": state["pool"].shape[1], "k": len(state["params"][0])}


def release(ctx, state, rec) -> None:
    state.pop("engine")


def picked(seed: int, sizes: np.ndarray, retired: np.ndarray,
           check_rows: int) -> list:
    """The longest retired request, then others in the seed's order, up
    to ``check_rows`` rows."""
    done = np.flatnonzero(np.isfinite(retired))
    if not len(done):
        return []
    longest = int(done[np.argmax(sizes[done])])
    rng = np.random.default_rng([seed, 2])
    out, rows = [longest], int(sizes[longest])
    for i in rng.permutation(done):
        if rows >= check_rows:
            break
        if i != longest:
            out.append(int(i))
            rows += int(sizes[i])
    return out


def compared_rows(state, rec, ids):
    rows = np.concatenate([state["requests"][i].rows for i in ids])
    served = np.concatenate([rec["scores"][i] for i in ids])
    return rows, served


def err_share(rows, served, params) -> float:
    """The largest |served - reference| over each row's float32 bound."""
    ref = reference.ref_logsumexp(reference.ref_weighted_logpdf(rows,
                                                                *params))
    tol = reference.score_tolerance(rows, params, ref)
    return float(np.max(np.abs(-np.asarray(served, np.float64) - ref) / tol))


@jax.jit
def _control_logprob(x, w, mu, var):
    inv = 1.0 / var
    lp = (matmul_high(x * x, (-0.5 * inv).T) + matmul_high(x, (mu * inv).T)
          - 0.5 * (jnp.sum(mu * mu * inv, axis=1)
                   + jnp.sum(jnp.log(var), axis=1)
                   + x.shape[1] * reference.LOG_2PI) + jnp.log(w))
    return jax.scipy.special.logsumexp(lp, axis=1)


def control_scores(rows, params):
    """The control: the reference's density put in the program's place in
    float32, by the matmul identity at the precision a step below the
    configuration's (``HIGH``, three bf16 passes, for ``HIGHEST``) ->
    anomaly scores."""
    w, mu, var = (jnp.asarray(p, jnp.float32) for p in params)
    return np.asarray(-_control_logprob(jnp.asarray(rows, jnp.float32),
                                        w, mu, var))


def check(ctx, state, rec) -> list:
    sub = rec["submitted"]
    retired = rec["retired"][:sub]
    sizes = state["sizes"][:sub]
    unanswered = int(np.sum(~np.isfinite(retired)))
    misaligned = sum(rec["scores"][i].shape != (sizes[i],)
                     for i in np.flatnonzero(np.isfinite(retired)))
    ids = picked(ctx.seed, sizes, retired, int(ctx.traffic["check_rows"]))
    share = err_share(*compared_rows(state, rec, ids), state["params"]) \
        if ids else float("inf")
    lim = ctx.limits
    return [{"name": "score_err_share", "value": share,
             "limit": float(lim["score_err_share"])},
            {"name": "unanswered", "value": float(unanswered),
             "limit": float(lim["unanswered"])},
            {"name": "misaligned", "value": float(misaligned),
             "limit": float(lim["misaligned"])}]
