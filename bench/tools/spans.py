#!/usr/bin/env python3
"""Run one cell traced, and read the program's own spans in its trace.

    python3 bench/tools/spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does, on the chips the cell
asks for and with the same profiler options, and prints its result line.
Then it prints one more JSON line, read from the same trace by
``lib.program``: the program's per-layer numbers (``readings``), the
window's idle gaps named by the innermost span open at their midpoint
(``named_gaps``: the longest, with where in that span each began, and the
idle time by naming span), the bounds on the device clock's offset
(``offset_ms``), the programs that took most device time (``programs``),
and the traced run's end-to-end numbers (``end_to_end``, which a traced
result line leaves out), to set beside an untraced run of the same seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
from lib import program as prog  # noqa: E402
from lib import registry  # noqa: E402
from lib import trace as tr  # noqa: E402


def top_programs(program, trace, lo, hi, top=10) -> list:
    dev = sorted(trace.device_ops)[0] if trace.device_ops else None
    per = {}
    for name, a, b in program.modules.get(dev, ()):
        if a >= lo and b <= hi:
            base = name.split("(", 1)[0]
            per[base] = per.get(base, 0) + (b - a)
    return [[n, ns / 1e9] for n, ns in
            sorted(per.items(), key=lambda kv: -kv[1])[:top]]


def traced_run(args) -> tuple[dict, dict]:
    """(the run's result line, what the program's spans give)."""
    seen = {}
    load, kind_of = tr.load, registry.kind

    def load_both(path):
        seen["program"] = prog.load(path)
        seen["trace"] = trace = load(path)
        return trace

    def kind_keeping_e2e(name):
        kind = kind_of(name)
        end_to_end = kind.end_to_end

        def keep(ctx, state, rec):
            seen["end_to_end"] = dict(end_to_end(ctx, state, rec))
            return seen["end_to_end"]

        kind.end_to_end = keep
        return kind

    tr.load, registry.kind = load_both, kind_keeping_e2e
    try:
        out = run.run(argparse.Namespace(
            workload=args.workload, seed=args.seed, seconds=args.seconds,
            trace=1))
    finally:
        tr.load, registry.kind = load, kind_of
    trace, program = seen["trace"], seen["program"]
    lo, hi = trace.window()
    extra = {"readings": prog.readings(trace, program, lo, hi),
             "named_gaps": prog.named_gaps(trace, program, lo, hi),
             "offset_ms": [None if b is None else b / 1e6
                           for b in program.offset_bounds],
             "programs": top_programs(program, trace, lo, hi),
             "end_to_end": seen.get("end_to_end", {})}
    return out, extra


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    try:
        out, extra = traced_run(args)
    except registry.BenchError as e:
        print(f"spans: {e}", file=sys.stderr)
        return run.EXIT_NO_RUN
    print(json.dumps(out), flush=True)
    print(json.dumps({"program": extra}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
