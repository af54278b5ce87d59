"""The reduction of the program's own spans and programs in a trace
(``lib.program``), on synthetic traces, on the recorded v5e trace, and
through ``bench/tools/spans.py`` on a CPU run at a test size."""
import functools
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import run
from lib import program as prog
from lib import registry
from lib import trace as tr
from cells import SIZES

RECORDED = Path(__file__).parent / "data"
TOOL = Path(__file__).resolve().parents[1] / "tools" / "spans.py"
US = 1000  # ns
# the rows a fit cell needs at test size to come out correct (as in
# test_faults.py)
FIT_SIZES = {"train_rows": 120000}


def _serving():
    """Two steps with work, one without; times in µs."""
    p = prog.Program()
    put = {"rows": 600, "rows_computed": 4096, "h2d_bytes": 1392640}
    full = {"rows": 4096, "rows_computed": 4096, "h2d_bytes": 1392640}
    for t0, admitted, wait, queued, fill in ((0, 2, 3000, 3, put),
                                             (200, 1, 500, 1, full)):
        p.spans += [
            ("repro.serve.step", t0, t0 + 100, {}),
            ("repro.serve.admit", t0, t0 + 10, {}),
            ("repro.serve.stage", t0 + 10, t0 + 20,
             {"admitted": admitted, "queue_wait_us": wait,
              "queued": queued}),
            ("repro.serve.put", t0 + 20, t0 + 40, fill),
            ("repro.serve.score", t0 + 40, t0 + 50, {}),
            ("repro.serve.fetch", t0 + 50, t0 + 90, {}),
            ("repro.serve.harvest", t0 + 90, t0 + 100, {})]
    p.spans += [("repro.serve.step", 400, 420, {}),
                ("repro.serve.admit", 400, 410, {}),
                ("repro.serve.stage", 410, 420,
                 {"admitted": 0, "queue_wait_us": 0, "queued": 0})]
    p.spans = [(n, a * US, b * US, c) for n, a, b, c in p.spans]
    p.spans.sort(key=lambda s: (s[1], -s[2]))
    return p


def test_serving_readings():
    got = prog.readings(tr.Trace(), _serving(), 0, 1000 * US)
    assert got == pytest.approx({
        "serve_steps": 2,
        "serve_step_span_ms": 0.1,
        # admit and stage together, per step with work
        "serve_stage_ms": 0.02, "serve_put_ms": 0.02,
        "serve_score_ms": 0.01, "serve_fetch_ms": 0.04,
        "serve_harvest_ms": 0.01,
        "slot_fill": 100 * (600 + 4096) / 8192,
        "queue_wait_ms": (3000 + 500) / 3 / 1e3,
        # the step without work is not counted
        "queue_depth": (3 + 1) / 2,
        "h2d_bytes_per_row": 2 * 1392640 / (600 + 4096)})


def test_serving_readings_keep_to_the_window():
    got = prog.readings(tr.Trace(), _serving(), 150 * US, 1000 * US)
    assert got["serve_steps"] == 1
    assert got["slot_fill"] == pytest.approx(100.0)


def _fedgen():
    """Two fits, each a local-fits program then two server programs;
    device times lag the host's by ``off``; times in µs."""
    t, p = tr.Trace(), prog.Program()
    off = 5
    slab = {"clients": 20, "rows": 400, "rows_computed": 1000, "lanes": 84,
            "lanes_computed": 128}
    t.spans = [("bench.window", 0, 10000), ("bench.fit", 100, 5000),
               ("bench.fit", 5000, 9000)]
    p.spans = [("repro.fedgen.local", 100, 400, slab),
               ("repro.fedgen.merge_sample", 2850, 2900, {"rows": 20000}),
               ("repro.fedgen.refit", 2900, 3600, {}),
               ("repro.fedgen.local", 5000, 5300, slab),
               ("repro.fedgen.merge_sample", 8000, 8050, {"rows": 18000})]
    p.modules["/device:TPU:0"] = [
        ("jit__train_locals_jit(1)", 150, 3000), ("jit__lambda(2)", 3100, 3500),
        ("jit__train_locals_jit(1)", 5100, 8000), ("jit_fit(3)", 8200, 8300)]
    t.device_ops["/device:TPU:0"] = [(n, a, b) for n, a, b in
                                     p.modules["/device:TPU:0"]]
    for d in (t.device_ops, p.modules):
        d["/device:TPU:0"] = [(n, (a - off) * US, (b - off) * US)
                              for n, a, b in d["/device:TPU:0"]]
    t.spans = [(n, a * US, b * US) for n, a, b in t.spans]
    p.spans = [(n, a * US, b * US, c) for n, a, b, c in p.spans]
    p.offset_ns = off * US
    return t, p


def test_fedgen_readings():
    t, p = _fedgen()
    got = prog.readings(t, p, 0, 10000 * US)
    assert got == pytest.approx({
        "slab_fill": 100 * 400 * 84 / (1000 * 128),
        "client_rows": 400 / 20,
        "synthetic_rows": (20000 + 18000) / 2,
        # device busy after the local fits, to the end of each bench.fit
        "server_ms": (0.4 + 0.1) / 2})


def test_gaps_are_named_by_the_innermost_span():
    t, p = _fedgen()
    gaps = dict((round(g[1] * 1e6), g[0]) for g in
                prog.named_gaps(t, p, 0, 10000 * US)["longest"])
    # on the host's clock: [3000, 3100] lies inside the refit; [3500, 5100]
    # in the first fit's bench span alone; [0, 150] before any fit
    assert gaps[100] == "repro.fedgen.refit"
    assert gaps[1600] == "bench.fit"
    assert gaps[150] == "no bench span"


def test_idle_time_by_naming_span():
    t, p = _fedgen()
    gaps = prog.named_gaps(t, p, 0, 10000 * US, top=2)
    assert gaps["by_span"] == pytest.approx({
        "no bench span": 1850e-6, "bench.fit": 1800e-6,
        "repro.fedgen.refit": 100e-6})
    # the longest gap opens no span; the next starts 3.4 ms into the
    # first fit's 4.9 ms
    assert gaps["longest"] == [
        ["no bench span", pytest.approx(1700e-6)],
        ["bench.fit", pytest.approx(1600e-6), pytest.approx(3.4),
         pytest.approx(4.9)]]


def test_open_spans_finds_the_innermost():
    t, p = tr.Trace(), prog.Program()
    t.spans = [("bench.window", 0, 100), ("bench.fit", 10, 90)]
    p.spans = [("repro.a", 10, 50, {}), ("repro.b", 10, 20, {}),
               ("repro.c", 60, 70, {})]
    index = prog.OpenSpans(t, p)
    assert [index.at(x)[2] if index.at(x) else None
            for x in (5, 10, 15, 30, 55, 65, 95)] == [
        None, "repro.b", "repro.b", "repro.a", "bench.fit", "repro.c", None]


def test_gaps_are_breakdowns_gaps_without_an_offset():
    t, p = _fedgen()
    p.offset_ns = 0.0
    want = tr.breakdown(t, 0, 10000 * US)["idle_gaps"]
    got = prog.named_gaps(t, p, 0, 10000 * US)["longest"]
    assert [g[1] for g in got] == pytest.approx([g[1] for g in want])
    # with no program span open at a gap, the name is the benchmark's
    p.spans = []
    assert [g[:2] for g in prog.named_gaps(t, p, 0, 10000 * US)["longest"]] \
        == want


def _dem():
    """Two DEM fits: init programs, then the round loop; times in µs."""
    t, p = tr.Trace(), prog.Program()
    slab = {"clients": 20, "rows": 708405, "rows_computed": 20 * 55457,
            "lanes": 38, "lanes_computed": 128}
    for t0, rounds in ((0, 4), (5000, 6)):
        p.spans += [("repro.rounds.init", t0 + 100, t0 + 200, {}),
                    ("repro.rounds.loop", t0 + 200, t0 + 3000, slab),
                    ("repro.rounds.finalize", t0 + 3000, t0 + 3100,
                     {"rounds": rounds})]
        p.modules.setdefault("/device:TPU:0", []).extend(
            [("jit__lambda(7)", t0 + 150, t0 + 1500),
             ("jit__iterate_jit(8)", t0 + 1600, t0 + 2900)])
        t.device_ops.setdefault("/device:TPU:0", []).extend(
            [("fusion", t0 + 150, t0 + 1000), ("fusion", t0 + 1100, t0 + 1500),
             ("while", t0 + 1600, t0 + 2900)])
    for d in (t.device_ops, p.modules):
        d["/device:TPU:0"] = [(n, a * US, b * US) for n, a, b in
                              d["/device:TPU:0"]]
    p.spans = [(n, a * US, b * US, c) for n, a, b, c in p.spans]
    return t, p


def test_dem_readings():
    t, p = _dem()
    got = prog.readings(t, p, 0, 10000 * US)
    assert got == pytest.approx({
        "slab_fill": 100 * 708405 * 38 / (20 * 55457 * 128),
        "client_rows": 708405 / 20,
        "round_ms": 2 * 1.3 / (4 + 6),
        # busy from the init span's start to the loop program's start
        "init_ms": (0.85 + 0.4 + 0.85 + 0.4) / 2})


def test_nothing_to_read_reads_nothing():
    t, _ = _dem()
    assert prog.readings(t, prog.Program(), 0, 10000 * US) == {}


@pytest.mark.skipif(not list(RECORDED.glob("*.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_trace_offset_puts_each_program_in_its_span():
    """On the recorded v5e trace a program's device start reads before the
    host enqueued it; moved by the least offset the runtime's events
    allow, each of the 15 program runs lies inside the ``bench.*`` span
    that launched it."""
    path = tr.find_xplane(str(RECORDED))
    t, p = tr.load(path), prog.load(path)
    least, most = p.offset_bounds
    assert 1.4e6 < least == p.offset_ns < most < 2.1e6
    runs = p.modules["/device:TPU:0"]
    assert len(runs) == 15

    def holder(a, b):
        return [n for n, s0, s1 in t.spans if s0 <= a and b <= s1]

    assert sum(bool(holder(a, b)) for _, a, b in runs) < 15
    assert all(len(holder(a + p.offset_ns, b + p.offset_ns)) == 1
               for _, a, b in runs)
    assert p.spans == [] and prog.readings(t, p, *_bounds(t)) == {}


def _bounds(t):
    return min(a for _, a, _ in t.spans), max(b for _, _, b in t.spans)


@pytest.mark.skipif(not list(RECORDED.glob("*.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_trace_readers_keep_their_values():
    """What the accepted readers read from the recorded trace."""
    t = tr.load(tr.find_xplane(str(RECORDED)))
    lo, hi = _bounds(t)
    layer = {"trace": t, "lo": lo, "hi": hi, "device_kind": "TPU v5 lite",
             "d": 84, "k": 10, "estep_rows": 3 * 4096,
             "retired": np.array([0.5, 1.0, np.inf]),
             "sizes": np.array([4096, 4096, 100]), "traced_s": 10.0}
    want = {"device_idle.fit": 24.684548126579397,
            "device_idle.serve_tail": 24.684548126579397,
            "estep_stats_roofline": 0.025261879054880865,
            "gmm_logpdf_roofline": 12.510538862744305}
    for name, value in want.items():
        assert registry.metric_reader(name).read(layer) == \
            pytest.approx(value, rel=1e-12), name
    gaps = tr.breakdown(t, lo, hi)["idle_gaps"]
    assert [g[0] for g in gaps[:3]] == ["bench.kmeans", "bench.estep",
                                        "bench.estep"]
    assert gaps[0][1] == pytest.approx(0.002063443)


def _tool():
    spec = importlib.util.spec_from_file_location("spans_tool", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SERVING = {"serve_stage_ms", "serve_put_ms", "serve_fetch_ms", "slot_fill",
           "queue_wait_ms", "queue_depth", "h2d_bytes_per_row"}


@pytest.mark.parametrize("workload,sizes,want", [
    ("wadi.fedgen", FIT_SIZES, {"slab_fill", "client_rows",
                                "synthetic_rows"}),
    ("smd.dem", FIT_SIZES, {"slab_fill", "client_rows"}),
    ("wadi.serve.flood", SIZES, SERVING),
    ("wadi.serve.steady", SIZES, SERVING),
])
def test_tool_reads_a_cpu_run(monkeypatch, capsys, workload, sizes, want):
    """A traced CPU run at a test size (no device plane, so nothing the
    device's clock gives) holds the program's spans and counters."""
    config, traffic_of = registry.config, registry.traffic

    def small_traffic(name):
        t = traffic_of(name)
        if t["kind"] == "serve":
            t.update(size_max=800, check_rows=5000, trace_seconds=1.0,
                     rate_per_s=200)
        return t

    monkeypatch.setattr(registry, "config",
                        lambda name: dict(config(name), **sizes))
    monkeypatch.setattr(registry, "traffic", small_traffic)
    monkeypatch.setattr(registry, "peak_for", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    import jax
    monkeypatch.setattr(run, "run", functools.partial(
        run.run, find_devices=lambda chips: jax.devices()))
    rc = _tool().main(["--workload", workload, "--seed", "3",
                       "--seconds", "2"])
    assert rc == 0
    result, extra = (json.loads(line) for line in
                     capsys.readouterr().out.strip().splitlines()[-2:])
    assert result["correct"]
    readings = extra["program"]["readings"]
    assert want <= set(readings)
    if "slab_fill" in want:
        # the CPU's reference E-step computes over the d features alone
        assert 0 < readings["slab_fill"] <= 100
    else:
        assert 0 < readings["slot_fill"] <= 100
    assert extra["program"]["end_to_end"]
