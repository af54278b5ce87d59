"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` alone. A device plane is one named
``/device:TPU:<n>``; its operations are the events of its ``XLA Ops``
line, each named by its HLO instruction. The benchmark's host spans are
the host events whose names start with ``bench.``, on the same clock.
Busy time is the union of the operation intervals inside the traced
window; the idle share is one minus busy over the window; a kernel's time
is the sum of the durations of the operations whose own name (the
instruction's, before its operands) holds the kernel's stable name.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    # per device: list of (name, start_ns, end_ns), sorted by start
    device_ops: dict = field(default_factory=dict)
    # host spans: list of (name, start_ns, end_ns)
    spans: list = field(default_factory=list)

    def window(self, name: str = "bench.window"):
        """(start_ns, end_ns) of the traced window span."""
        for n, t0, t1 in self.spans:
            if n == name:
                return t0, t1
        raise ValueError(f"no {name} span in the trace")


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.device_ops[plane.name] = sorted(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out.spans.append((ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    out.spans.sort(key=lambda s: s[1])
    return out


def _clip(intervals, lo, hi):
    for _, a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def busy_ns(ops, lo: int, hi: int) -> int:
    """Length of the union of the operation intervals inside [lo, hi]."""
    total, cur_a, cur_b = 0, None, None
    for a, b in sorted(_clip(ops, lo, hi)):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def busy_s(trace: Trace, lo: int, hi: int) -> float:
    """Device busy seconds in [lo, hi], averaged over the chips used: those
    that ran an operation in it. A cell that holds the whole host for
    steadiness and serves from one chip leaves the others idle."""
    busy = [b for b in (busy_ns(ops, lo, hi)
                        for ops in trace.device_ops.values()) if b]
    return sum(busy) / len(busy) / 1e9 if busy else 0.0


def op_name(event_name: str) -> str:
    """An operation's event is named by its whole HLO instruction, operands
    and all; its own name is the part before " = "."""
    return event_name.split(" = ", 1)[0]


def kernel_time(trace: Trace, stable_name: str, lo: int,
                hi: int) -> tuple[int, float]:
    """(calls, seconds) of the kernel's operations inside [lo, hi] over
    every chip, whatever program each ran in."""
    calls, seconds = 0, 0.0
    for ops in trace.device_ops.values():
        for n, a, b in ops:
            if stable_name in op_name(n) and a >= lo and b <= hi:
                calls += 1
                seconds += (b - a) / 1e9
    return calls, seconds


def _span_at(spans, t: int) -> str:
    """The innermost host span open at ``t`` (the latest-starting one)."""
    best = None
    for n, a, b in spans:
        if a <= t <= b and n != "bench.window":
            best = n
        elif a > t:
            break
    return best or "no bench span"


def breakdown(trace: Trace, lo: int, hi: int, top: int = 10) -> dict:
    """The device operations that took most time (an operation that holds
    others, such as a while loop, counts its whole length), and the
    longest idle gaps named by what the host was doing, for the first
    chip."""
    if not trace.device_ops:
        return {"device_ops": [], "idle_gaps": []}
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    per_op: dict = {}
    for n, a, b in ops:
        if a >= lo and b <= hi:
            n = op_name(n)
            per_op[n] = per_op.get(n, 0) + (b - a)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps, last = [], lo
    for a, b in sorted(_clip(ops, lo, hi)):
        if a > last:
            gaps.append((last, a))
        last = max(last, b)
    if hi > last:
        gaps.append((last, hi))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, ns / 1e9] for n, ns in top_ops],
            "idle_gaps": [[_span_at(trace.spans, (a + b) // 2), (b - a) / 1e9]
                          for a, b in longest]}
