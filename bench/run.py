#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names its kind
(``bench/kinds/<kind>.py``), which makes the data from ``--seed``, warms
up, drives the system for ``--seconds`` and checks its answers against
the float64 reference (``bench/lib/reference.py``) under the cell's
limits (``bench/limits/<workload>.json``). With ``--trace 0`` the line
holds the cell's end-to-end metrics; with ``--trace 1`` the window is
traced by ``jax.profiler`` and the line holds the per-layer metrics, each
read by ``bench/metrics/<metric>.py``.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

from lib import registry  # noqa: E402
from lib.clock import CompileClock, Spans  # noqa: E402
from lib.registry import BenchError  # noqa: E402

EXIT_NO_RUN = 2


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a kind of traffic is handed: the cell's files, seed and clocks."""
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    gen: object
    key: object
    spans: Spans
    compile: CompileClock


def require_chips(chips: int):
    """The devices JAX finds, or BenchError when they are not TPUs or are
    fewer than the cell asks for. A CPU run is never a measurement."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); this benchmark runs on "
                         f"the chip only")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the one ``repro.compile_cache`` names), whatever the
    environment says: a run never shares a cache with another checkout,
    and every run after a cell's first loads its programs from there."""
    import jax
    from repro.compile_cache import CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    # every program this run compiles goes to the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: with a size limit from the environment, JAX reads an
    # access-time file beside every entry before it writes, and an entry
    # that came without one fails every write of the run
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CHECKOUT_CACHE_DIR)


def _selected(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def run(args, find_devices=require_chips) -> dict:
    bench = registry.benchmark()
    wl = registry.workload(bench, args.workload)
    cfg = registry.config(wl["config"])
    traffic = registry.traffic(wl["traffic"])
    kind = registry.kind(traffic["kind"])
    gen = registry.generator(cfg["generator"])
    limits = registry.limits(wl["name"])

    devices = find_devices(int(wl["chips"]))
    import jax
    from lib.datasets import run_key
    log(f"compile cache: {enable_cache()}")
    ctx = Context(cfg=cfg, traffic=traffic, limits=limits,
                  seed=int(args.seed), seconds=float(args.seconds),
                  gen=gen, key=run_key(args.seed),
                  spans=Spans(traced=bool(args.trace)),
                  compile=CompileClock())
    state = kind.setup(ctx)
    setup_s = time.perf_counter() - T0
    setup_compile = ctx.compile.seconds
    log(f"setup {setup_s:.3f} s (compile {setup_compile:.3f} s)")

    # the window keeps every answer for the check afterwards; a full
    # collection over those would stall the system under test, so the
    # collector waits until the window has closed
    gc.collect()
    gc.disable()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    tracing = bool(trace_dir)

    def stop_trace():
        """Called by the kind when the traced part of the window ends: at
        the window's close, or earlier where the mix traces only its first
        ``trace_seconds``. Later calls do nothing."""
        nonlocal tracing
        if tracing:
            tracing = False
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        window_span = ctx.spans.span("bench.window")
        window_span.__enter__()
    rec = kind.window(ctx, state, stop_trace)
    stop_trace()
    gc.enable()
    window_compile = ctx.compile.seconds - setup_compile
    log(f"compile inside the window: {window_compile:.3f} s")
    dev = devices[0]
    stats = [d.memory_stats() or {} for d in devices[: int(wl["chips"])]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    e2e = kind.end_to_end(ctx, state, rec)
    attempted, failed = kind.counts(ctx, state, rec)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {}
    if trace_dir:
        from lib import trace as tr
        trace = tr.load(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = trace.window()
        device["busy_s"] = tr.busy_s(trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        layer = kind.layer_context(ctx, state, rec)
        layer.update(trace=trace, lo=lo, hi=hi, device_kind=dev.device_kind)
        metrics = {}
        for m in _selected(bench["per_layer"], wl["name"]):
            value = registry.metric_reader(m["name"]).read(layer)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(trace, lo, hi)
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in _selected(bench["end_to_end"], wl["name"])}

    kind.release(ctx, state, rec)
    gc.collect()
    checks = kind.check(ctx, state, rec)
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": correct, "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        out = run(args)
    except BenchError as e:
        log(f"bench: {e}")
        return EXIT_NO_RUN
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
