"""The reduction from a profiler trace to device metrics."""
import glob
from pathlib import Path

import pytest

from lib import registry
from lib import trace as tr

RECORDED = Path(__file__).parent / "data"


def _trace():
    """Two chips; times in ns. Window [0, 1000]."""
    t = tr.Trace()
    t.device_ops = {
        "/device:TPU:0": [("%fusion.1 = f32[2] fusion(f32[2] %p)", 100, 300),
                          ("%estep_stats_pallas.3 = (f32[1,128]) custom-call()",
                           250, 400),
                          ("%copy.2 = f32[2] copy(f32[2] %estep_stats_pallas.3)",
                           600, 700),
                          ("%estep_stats_pallas.4 = (f32[1,128]) custom-call()",
                           900, 1200)],
        "/device:TPU:1": [("fusion.1", 0, 500)],
    }
    t.spans = [("bench.window", 0, 1000), ("bench.fit", 0, 520),
               ("bench.fit", 560, 1000), ("bench.engine.step", 720, 880)]
    return t


def test_busy_is_the_union_of_operation_intervals():
    ops = _trace().device_ops["/device:TPU:0"]
    # [100, 400] union [600, 700] union [900, 1000] inside the window
    assert tr.busy_ns(ops, 0, 1000) == 300 + 100 + 100


@pytest.mark.parametrize("idle_chips", [0, 2])
def test_busy_seconds_average_over_chips(idle_chips):
    # chip 0: 500 ns, chip 1: 500 ns; chips that ran nothing in the window
    # were not used and do not count
    t = _trace()
    for n in range(idle_chips):
        t.device_ops[f"/device:TPU:{2 + n}"] = [("fusion.9", 1500, 1600)]
    assert tr.busy_s(t, 0, 1000) == pytest.approx(500e-9)


def test_idle_share_reader():
    t = _trace()
    layer = {"trace": t, "lo": 0, "hi": 1000}
    value = registry.metric_reader("device_idle.fit").read(layer)
    assert value == pytest.approx(50.0)


def test_kernel_time_by_name():
    calls, seconds = tr.kernel_time(_trace(), "estep_stats_pallas", 0, 1000)
    # the copy names the kernel only among its operands; the second kernel
    # event ends after the window; both are left out
    assert (calls, seconds) == (1, pytest.approx(150e-9))
    calls, seconds = tr.kernel_time(_trace(), "estep_stats_pallas", 0, 2000)
    assert (calls, seconds) == (2, pytest.approx(450e-9))


def test_breakdown_names_gaps_by_host_span():
    b = tr.breakdown(_trace(), 0, 1000)
    # operations that end after the window are left out
    assert b["device_ops"] == [["%fusion.1", pytest.approx(200e-9)],
                               ["%estep_stats_pallas.3", pytest.approx(150e-9)],
                               ["%copy.2", pytest.approx(100e-9)]]
    gaps = b["idle_gaps"]
    # gaps on chip 0: [0,100] [400,600] [700,900]
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 200e-9, 100e-9])
    assert {g[0] for g in gaps} == {"bench.fit", "bench.engine.step"}


def test_roofline_reader_counts_unpadded_work():
    """Every call of the kernel counts its time, whatever program it ran
    in; the work is the E-step rows the fits report."""
    t = _trace()
    layer = {"trace": t, "lo": 0, "hi": 2000, "device_kind": "TPU v5 lite",
             "d": 84, "k": 10, "estep_rows": 3000}
    share = registry.metric_reader("estep_stats_roofline").read(layer)
    kc = registry.kernel_count("estep_stats")
    peak = registry.peak_for("TPU v5 lite")
    f, b = kc.count(3000, 84, 10)
    floor = max(f / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
    assert share == pytest.approx(100 * floor / 450e-9)


def test_roofline_reader_is_silent_without_the_kernel():
    t = _trace()
    t.device_ops["/device:TPU:0"] = [
        op for op in t.device_ops["/device:TPU:0"]
        if "estep_stats_pallas." not in tr.op_name(op[0])]
    layer = {"trace": t, "lo": 0, "hi": 2000, "device_kind": "TPU v5 lite",
             "d": 84, "k": 10, "estep_rows": 1000}
    assert registry.metric_reader("estep_stats_roofline").read(layer) is None


@pytest.mark.parametrize("reader", ["serve_step_ms.tail", "serve_step_ms.rate"])
def test_step_reader_keeps_to_the_traced_part(reader):
    """Steps that end after the trace stopped are left out: the stop
    blocks the loop, and the backlog it leaves is a load the cell never
    offers."""
    layer = {"steps": [(0.0, 0.002, 3), (1.0, 1.004, 2), (9.99, 10.5, 9),
                       (12.0, 12.03, 8)],
             "traced_s": 10.0, "seconds": 51.0}
    value = registry.metric_reader(reader).read(layer)
    assert value == pytest.approx(3.0)


@pytest.mark.skipif(not glob.glob(str(RECORDED / "*.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_chip_trace():
    """A small trace recorded on one v5e chip (three calls each of the
    vmapped E-step, the log density and the k-means kernels, each inside
    a ``bench.*`` span)."""
    t = tr.load(tr.find_xplane(str(RECORDED)))
    assert list(t.device_ops) == ["/device:TPU:0"]
    lo = min(a for _, a, _ in t.spans)
    hi = max(b for _, _, b in t.spans)
    busy = tr.busy_s(t, lo, hi)
    assert 0 < busy < (hi - lo) / 1e9
    for name in ("estep_stats_pallas", "gmm_logpdf_pallas"):
        calls, seconds = tr.kernel_time(t, name, lo, hi)
        assert calls == 3
        assert 0 < seconds <= busy
