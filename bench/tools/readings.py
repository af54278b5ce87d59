#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds, for setting its limits.

    python3 bench/tools/readings.py --workload wadi.fedgen --seeds 11,12,13
    python3 bench/tools/readings.py --workload wadi.fedgen --seeds 11,12,13 --control

One process, one line of JSON per seed. A fit cell runs one federation
per seed at the cell's own size, on rows and a key made from that seed
(the cell's own runs fix both by its mix's ``work_seed``), and reads the
numbers of that answer. A serving cell offers its mix for
``--seconds`` at its own rate and reads the numbers of its retired
requests, and beside them the control's: the reference's float32
density at ``HIGH`` over the same rows.

``--control`` (fit cells) runs the federations with the program's
contractions a step below the configuration's precision: the E-step and
M-step on the program's plain jnp path (``FitConfig(backend=
"reference")``) with its products at ``HIGH``, three bf16 passes,
instead of ``HIGHEST`` (``bench/lib/control.py``). ``--fault <name>`` plants one of
``bench/lib/faults.py``'s faults under the timed path first.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
from lib import faults, registry  # noqa: E402
from lib.control import lower_precision  # noqa: E402
from lib.clock import CompileClock, Spans  # noqa: E402
from lib.datasets import run_key  # noqa: E402


def context(wl, seed, seconds, traffic=None):
    cfg = registry.config(wl["config"])
    traffic = traffic or registry.traffic(wl["traffic"])
    return run.Context(cfg=cfg, traffic=traffic,
                       limits=registry.limits(wl["name"]), seed=seed,
                       seconds=seconds,
                       gen=registry.generator(cfg["generator"]),
                       key=run_key(seed), spans=Spans(),
                       compile=CompileClock())


def fit_reading(wl, seed, control: bool) -> dict:
    kind = registry.kind("fit")
    traffic = dict(registry.traffic(wl["traffic"]))
    if control:
        traffic["fit_config"] = dict(traffic["fit_config"],
                                     backend="reference")
    ctx = context(wl, seed, 1e-3, traffic)
    state = {"split": kind.make_split(ctx, seed), "runner": kind._runner(ctx),
             "keys": kind.fit_keys(seed, 1, seed)}
    rec = kind.window(ctx, state, lambda: None)
    fit_s = rec["fits"][0]["t1"] - rec["fits"][0]["t0"]
    kind.release(ctx, state, rec)
    got = kind.numbers(state["checked"], state["rows"], state["sizes"])
    return dict(got, fit_s=fit_s, rounds=rec["fits"][0]["rounds"])


def serve_reading(wl, seed, seconds) -> dict:
    kind = registry.kind("serve")
    ctx = context(wl, seed, seconds)
    state = kind.setup(ctx)
    rec = kind.window(ctx, state, lambda: None)
    kind.release(ctx, state, rec)
    sub = rec["submitted"]
    ids = kind.picked(seed, state["sizes"][:sub], rec["retired"][:sub],
                      int(ctx.traffic["check_rows"]))
    rows, served = kind.compared_rows(state, rec, ids)
    return {"score_err_share": kind.err_share(rows, served, state["params"]),
            "control_score_err_share": kind.err_share(
                rows, kind.control_scores(rows, state["params"]),
                state["params"]),
            "rows": int(len(rows)), "requests": len(ids), "offered": sub}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--control", action="store_true")
    p.add_argument("--fault", default=None)
    args = p.parse_args()
    wl = registry.workload(registry.benchmark(), args.workload)
    run.require_chips(int(wl["chips"]))
    run.enable_cache()
    if args.control:
        lower_precision(setattr)
    traffic = registry.traffic(wl["traffic"])
    kind = traffic["kind"]
    if args.fault:
        table = dict(faults.FIT, **faults.MERGE) if kind == "fit" \
            else faults.SERVE
        table[args.fault](setattr, traffic.get("strategy"))
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if kind == "fit":
            out = fit_reading(wl, seed, args.control)
        else:
            out = serve_reading(wl, seed, args.seconds)
        out.update(workload=wl["name"], seed=seed, control=args.control,
                   fault=args.fault, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
