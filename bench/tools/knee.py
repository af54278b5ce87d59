#!/usr/bin/env python3
"""Find the knee of a serving cell once, by a sweep on the chip.

    python3 bench/tools/knee.py --workload wadi.serve.steady --seconds 8

First a flood well above capacity (``--flood`` requests a second) gives
the requests the engine completes per second; then the cell's mix is offered at fractions of
that rate, each for ``--seconds``, and each line reports the backlog
left at the window's close and the latency tail. The knee is the highest
rate whose backlog stays within the slot pool. The cells' rates are
written into their traffic files by hand from this output.
"""
from __future__ import annotations

import argparse
import gc
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import run  # noqa: E402
from lib import registry  # noqa: E402
from lib.clock import CompileClock, Spans  # noqa: E402
from lib.datasets import run_key  # noqa: E402


def offer(wl, rate, seconds, seed):
    cfg = registry.config(wl["config"])
    traffic = dict(registry.traffic(wl["traffic"]), rate_per_s=rate,
                   drain_s=0.0)
    kind = registry.kind(traffic["kind"])
    ctx = run.Context(cfg=cfg, traffic=traffic, limits={},
                      seed=seed, seconds=seconds,
                      gen=registry.generator(cfg["generator"]),
                      key=run_key(seed), spans=Spans(), compile=CompileClock())
    state = kind.setup(ctx)
    gc.collect()
    gc.freeze()
    rec = kind.window(ctx, state, lambda: None)
    gc.unfreeze()
    due, retired = state["due"], rec["retired"]
    sub = rec["submitted"]
    done = np.isfinite(retired[:sub]) & (retired[:sub] <= seconds)
    lat = (retired[:sub][done] - due[:sub][done]) * 1e3
    return {"rate": rate, "offered": sub, "completed": int(done.sum()),
            "completed_per_s": done.sum() / seconds,
            "rows_per_s": state["sizes"][:sub][done].sum() / seconds,
            "backlog": int(sub - done.sum()),
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
            "steps": len(rec["steps"])}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--seed", type=int, default=123457)
    p.add_argument("--flood", type=float, default=8000.0)
    p.add_argument("--fractions", default="0.8,1.0,1.2,1.4,1.6,1.8,2.0")
    args = p.parse_args()
    wl = registry.workload(registry.benchmark(), args.workload)
    run.require_chips(int(wl["chips"]))
    run.enable_cache()
    flood = offer(wl, args.flood, args.seconds, args.seed)
    print("flood", flood, flush=True)
    capacity = flood["completed_per_s"]
    for f in (float(x) for x in args.fractions.split(",")):
        print(f"{f:.2f} x capacity", offer(wl, f * capacity, args.seconds,
                                           args.seed + 1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
