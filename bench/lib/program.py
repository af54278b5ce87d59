"""The program's own spans and programs in a profiler trace.

The program marks its phases with ``jax.profiler.TraceAnnotation``\\ s
named ``repro.*``, with its counters among each annotation's arguments
(DESIGN.md §13); they land in the trace file on the device's clock. This
module reads them, with their counters as a dict, and each device's ``XLA
Modules`` line (one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``), and reduces them to per-layer numbers.
Each reduction returns None where the trace holds nothing for it, as a
trace of a program without these spans does.

The device's timestamps run behind the host's: on one v5e a program's
device start reads about 1.5 ms before the host enqueued it (the
recorded trace in ``bench/tests/data``). ``load`` bounds that offset by
the runtime's own host events, matched to each program run by its
``run_id``: a run cannot start before its ``DoEnqueueProgram`` nor end
after its ``CompleteCallbacks``. The least offset that keeps every run
after its enqueue moves device times onto the host's clock wherever they
meet host spans here.

``lib.trace.load`` keeps neither, so the benchmark's result line does not
hold these numbers yet; ``bench/tools/spans.py`` prints them for one run
(PERF.md §7 names the edits that would put them in the result line).
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from lib.trace import busy_ns

PREFIX = "repro."
MODULES_LINE = "XLA Modules"
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
ROUND_LOOP = "jit__iterate_jit"
LOCAL_FITS = "jit__train_locals_jit"


@dataclass
class Program:
    # (name, start_ns, end_ns, counters), sorted by start, outer first
    spans: list = field(default_factory=list)
    # per device: (program name, start_ns, end_ns), sorted by start, on
    # the device's clock
    modules: dict = field(default_factory=dict)
    # add to a device time to read it on the host's clock; and the bounds
    # the runtime's events put on that offset (None where none matched)
    offset_ns: float = 0.0
    offset_bounds: tuple = (None, None)


def _offset_bounds(runs: dict, enqueued: dict, completed: dict) -> tuple:
    """(least, most) offset consistent with every matched program run:
    each starts after its earliest enqueue and ends before its earliest
    completion callback, on the host's clock."""
    least = [min(enqueued[r]) - a for r, (a, _) in runs.items()
             if r in enqueued]
    most = [min(completed[r]) - b for r, (_, b) in runs.items()
            if r in completed]
    return (max(least) if least else None, min(most) if most else None)


def load(path: str) -> Program:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = Program()
    run_ids, enqueued, completed = {}, {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                mods = []
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    mods.append((ev.name, ev.start_ns, end))
                    run_ids.setdefault(plane.name, {})[
                        dict(ev.stats).get("run_id")] = (ev.start_ns, end)
                out.modules[plane.name] = sorted(mods, key=lambda m: m[1])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
                elif ev.name in (ENQUEUE, COMPLETE):
                    stats = dict(ev.stats)
                    if stats.get("device_ordinal", 0) == 0 and \
                            "run_id" in stats:
                        to = enqueued if ev.name == ENQUEUE else completed
                        to.setdefault(stats["run_id"], []).append(
                            ev.start_ns)
    out.spans.sort(key=lambda s: (s[1], -s[2]))
    runs = run_ids.get(min(out.modules), {}) if out.modules else {}
    out.offset_bounds = _offset_bounds(runs, enqueued, completed)
    out.offset_ns = out.offset_bounds[0] or 0.0
    return out


def _inside(spans, name, lo, hi):
    return [s for s in spans if s[0] == name and s[1] >= lo and s[2] <= hi]


def _first_device(trace):
    return sorted(trace.device_ops)[0] if trace.device_ops else None


# -- idle gaps -------------------------------------------------------------

def idle_gaps(ops, lo: int, hi: int) -> list:
    """Every interval of [lo, hi] in which no operation ran, as (start,
    end), in time order (the gaps ``lib.trace.breakdown`` ranks)."""
    gaps, last = [], lo
    for _, a, b in sorted(ops, key=lambda o: o[1]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > last:
            gaps.append((last, a))
        last = max(last, b)
    if hi > last:
        gaps.append((last, hi))
    return gaps


class OpenSpans:
    """The benchmark's spans (``bench.*``, but not the window) and the
    program's (``repro.*``), to find the innermost one open at a time:
    the latest-starting of those that hold it."""

    def __init__(self, trace, program: Program):
        self.spans = sorted(
            ((a, b, name) for name, a, b, *_ in
             list(trace.spans) + list(program.spans)
             if name != "bench.window"), key=lambda s: (s[0], -s[1]))
        self.starts = [a for a, _, _ in self.spans]
        self.reach, last = [], float("-inf")  # latest end so far
        for _, b, _ in self.spans:
            last = max(last, b)
            self.reach.append(last)

    def at(self, t):
        """(start, end, name) of the innermost span open at ``t``, or
        None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] >= t:
            if self.spans[i][1] >= t:
                return self.spans[i]
            i -= 1
        return None


def named_gaps(trace, program: Program, lo: int, hi: int,
               top: int = 10) -> dict:
    """The first chip's idle time in the window [lo, hi] (host clock),
    each gap named by the innermost span open at its midpoint, or "no
    bench span" -> {"longest": the ``top`` longest as [[name, seconds,
    milliseconds from that span's start to the gap's start, the span's
    milliseconds], ...] (the last two where a span is open; the first two
    are what ``lib.trace.breakdown`` gives), "by_span": {name: idle
    seconds}, most first}."""
    dev = _first_device(trace)
    if dev is None:
        return {"longest": [], "by_span": {}}
    off, index = program.offset_ns, OpenSpans(trace, program)
    gaps, by_span = [], {}
    for a, b in idle_gaps(trace.device_ops[dev], lo - off, hi - off):
        seconds, span = (b - a) / 1e9, index.at((a + b) / 2 + off)
        name = span[2] if span else "no bench span"
        by_span[name] = by_span.get(name, 0.0) + seconds
        gaps.append([name, seconds] + ([(a + off - span[0]) / 1e6,
                                        (span[1] - span[0]) / 1e6]
                                       if span else []))
    return {"longest": sorted(gaps, key=lambda g: -g[1])[:top],
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1]))}


# -- serving -----------------------------------------------------------------

def serving_steps(program: Program, lo: int, hi: int) -> list:
    """The scoring steps with work inside [lo, hi]: for each
    ``repro.serve.step`` that holds a ``repro.serve.put``, its own span and
    its phases' as {"step" or phase: [(start, end, counters), ...]}."""
    steps = []
    phases = [s for s in program.spans if s[0].startswith("repro.serve.")
              and s[0] != "repro.serve.step" and s[1] >= lo and s[2] <= hi]
    j = 0
    for _, a, b, c in _inside(program.spans, "repro.serve.step", lo, hi):
        while j < len(phases) and phases[j][1] < a:
            j += 1
        step = {"step": [(a, b, c)]}
        k = j
        while k < len(phases) and phases[k][1] <= b:
            name, s0, s1, c = phases[k]
            step.setdefault(name.rsplit(".", 1)[1], []).append((s0, s1, c))
            k += 1
        if "put" in step:
            steps.append(step)
    return steps


def phase_ms(steps: list, *phases: str):
    """Mean milliseconds per step of the named phases, together."""
    if not steps:
        return None
    ns = sum(b - a for step in steps for p in phases
             for a, b, _ in step.get(p, ()))
    return ns / 1e6 / len(steps)


def counter_ratio(steps: list, phase: str, num: str, den: str):
    """Σ ``num`` / Σ ``den`` over the phase's spans in the steps."""
    spans = [c for step in steps for _, _, c in step.get(phase, ())]
    total = sum(c.get(den, 0) for c in spans)
    if not total:
        return None
    return sum(c.get(num, 0) for c in spans) / total


def queue_depth(steps: list):
    """Mean requests left in the queue after each step's admission: a
    backlog that grows through a window says the offered load is over
    what the engine sustains."""
    stages = [c for step in steps for _, _, c in step.get("stage", ())
              if "queued" in c]
    return sum(c["queued"] for c in stages) / len(stages) if stages \
        else None


def queue_wait_ms(steps: list):
    """Mean milliseconds a request admitted in these steps waited in the
    queue, from submit to admission."""
    ratio = counter_ratio(steps, "stage", "queue_wait_us", "admitted")
    return None if ratio is None else ratio / 1e3


# -- fitting -----------------------------------------------------------------

SLAB_SPANS = ("repro.fedgen.local", "repro.rounds.loop")
SLAB_COUNTERS = ("rows", "lanes", "rows_computed", "lanes_computed")


def slab_fill(program: Program, lo: int, hi: int):
    """Useful share of the client slab the local fits and round loops
    compute over, in %: Σ rows·lanes / Σ rows_computed·lanes_computed."""
    spans = [c for name in SLAB_SPANS
             for _, _, _, c in _inside(program.spans, name, lo, hi)
             if all(k in c for k in SLAB_COUNTERS)]
    den = sum(c["rows_computed"] * c["lanes_computed"] for c in spans)
    if not den:
        return None
    return 100.0 * sum(c["rows"] * c["lanes"] for c in spans) / den


def client_rows(program: Program, lo: int, hi: int):
    """Mean real rows a client holds in the local fits and round loops:
    Σ rows / Σ clients. Beside the slab's rows per client (``rows_computed``
    / ``clients``), the padded size the largest client sets, it says how
    far the clients' sizes spread; source clients, which count no padded
    slab, report it too."""
    spans = [c for name in SLAB_SPANS
             for _, _, _, c in _inside(program.spans, name, lo, hi)
             if "rows" in c and c.get("clients")]
    if not spans:
        return None
    return sum(c["rows"] for c in spans) / sum(c["clients"] for c in spans)


def synthetic_rows(program: Program, lo: int, hi: int):
    """Mean synthetic rows FedGenGMM's server draws and refits on per fit
    (``rows`` on ``repro.fedgen.merge_sample``): the size of the server
    phase's work."""
    rows = [c["rows"] for _, _, _, c in
            _inside(program.spans, "repro.fedgen.merge_sample", lo, hi)
            if "rows" in c]
    return sum(rows) / len(rows) if rows else None


def _runs(trace, program: Program, prefix: str) -> list:
    """The runs of one program on the first chip, on the host's clock."""
    dev, off = _first_device(trace), program.offset_ns
    return [(a + off, b + off) for name, a, b in program.modules.get(dev, ())
            if name.startswith(prefix + "(")]


def _busy_ms(trace, program: Program, a: float, b: float) -> float:
    """Device busy milliseconds of the first chip in [a, b], host clock."""
    off = program.offset_ns
    return busy_ns(trace.device_ops[_first_device(trace)], a - off,
                   b - off) / 1e6


def round_ms(trace, program: Program, lo: int, hi: int):
    """Device milliseconds of the jitted round loop per round: the
    ``_iterate_jit`` runs that end inside each ``repro.rounds.loop`` span
    (the span waits for the loop's result), over the rounds the following
    ``repro.rounds.finalize`` counts."""
    runs = _runs(trace, program, ROUND_LOOP)
    finals = _inside(program.spans, "repro.rounds.finalize", lo, hi)
    ns, rounds = 0.0, 0
    for _, a, b, _ in _inside(program.spans, "repro.rounds.loop", lo, hi):
        mine = [(r0, r1) for r0, r1 in runs if a <= r1 <= b]
        after = [c for _, f0, _, c in finals if f0 >= b]
        if mine and after and "rounds" in after[0]:
            ns += sum(r1 - r0 for r0, r1 in mine)
            rounds += after[0]["rounds"]
    return ns / 1e6 / rounds if rounds else None


def init_ms(trace, program: Program, lo: int, hi: int):
    """Device busy milliseconds per fit from the start of
    ``repro.rounds.init`` to the start of the next round loop run: the
    initialisation, which the device runs before the loop."""
    starts = [r0 for r0, _ in _runs(trace, program, ROUND_LOOP)]
    total, fits = 0.0, 0
    for _, a, _, _ in _inside(program.spans, "repro.rounds.init", lo, hi):
        start = next((r0 for r0 in starts if r0 >= a), None)
        if start is not None and start <= hi:
            total += _busy_ms(trace, program, a, start)
            fits += 1
    return total / fits if fits else None


def server_ms(trace, program: Program, lo: int, hi: int,
              fit_span: str = "bench.fit"):
    """Device busy milliseconds per FedGenGMM fit after its local fits:
    from the end of the fit's last ``_train_locals_jit`` run to the end of
    the span around the fit (unstacking, merge, sample and the server
    refit, all of which the span waits for)."""
    runs = _runs(trace, program, LOCAL_FITS)
    total, fits = 0.0, 0
    for name, a, b in trace.spans:
        if name != fit_span or a < lo or b > hi:
            continue
        ends = [r1 for _, r1 in runs if a <= r1 <= b]
        if ends:
            total += _busy_ms(trace, program, ends[-1], b)
            fits += 1
    return total / fits if fits else None


def readings(trace, program: Program, lo: int, hi: int) -> dict:
    """Every reduction that finds something in [lo, hi], by name."""
    steps = serving_steps(program, lo, hi)
    fill = counter_ratio(steps, "put", "rows", "rows_computed")
    out = {
        "serve_step_span_ms": phase_ms(steps, "step"),
        "serve_stage_ms": phase_ms(steps, "admit", "stage"),
        "serve_put_ms": phase_ms(steps, "put"),
        "serve_score_ms": phase_ms(steps, "score"),
        "serve_fetch_ms": phase_ms(steps, "fetch"),
        "serve_harvest_ms": phase_ms(steps, "harvest"),
        "serve_steps": len(steps) or None,
        "slot_fill": None if fill is None else 100.0 * fill,
        "queue_wait_ms": queue_wait_ms(steps),
        "queue_depth": queue_depth(steps),
        "h2d_bytes_per_row": counter_ratio(steps, "put", "h2d_bytes",
                                           "rows"),
        "slab_fill": slab_fill(program, lo, hi),
        "client_rows": client_rows(program, lo, hi),
        "synthetic_rows": synthetic_rows(program, lo, hi),
        "round_ms": round_ms(trace, program, lo, hi),
        "init_ms": init_ms(trace, program, lo, hi),
        "server_ms": server_ms(trace, program, lo, hi),
    }
    return {k: v for k, v in out.items() if v is not None}
