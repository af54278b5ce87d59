"""The four-chip DEM cell (``wadi.dem.sharded4``, kind ``fit_mesh``) on the
CPU at a test size, and the reader of its collective time.

The cell needs a mesh of four devices, so its runs go to a child process
with four host devices forced before JAX starts (the test process keeps
one). In it the cell runs sound, a step below its precision (the
control, ``lib.control``) and with each fit fault of ``lib.faults``
planted; only the sound run comes out correct."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from lib import registry

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = ["bench", "bench/tests", "src"]
    import jax
    from cells import run_small
    from lib import control, faults

    class Patch:
        def __init__(self):
            self.undo = []

        def setattr(self, owner, name, value):
            self.undo.append((owner, name, getattr(owner, name)))
            setattr(owner, name, value)

        def restore(self):
            for owner, name, value in reversed(self.undo):
                setattr(owner, name, value)
            self.undo = []

    def cell(plant=None, traffic=None):
        patch = Patch()
        jax.clear_caches()
        if plant is not None:
            plant(patch.setattr)
        try:
            out = run_small(patch, "wadi.dem.sharded4",
                            sizes={"train_rows": 120000}, seconds=1.0,
                            traffic=traffic)
        finally:
            patch.restore()
        return {"correct": out["correct"], "checks": out["checks"],
                "chips": out["device"]["count"]}

    got = {"sound": cell(),
           "control": cell(control.lower_precision,
                           {"fit_config": {"backend": "reference"}})}
    for name, fault in faults.FIT.items():
        got[name] = cell(lambda patch: fault(patch, "dem"))
    print(json.dumps(got))
""")


@pytest.fixture(scope="module")
def mesh_runs():
    root = registry.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=root, env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_mesh_run_is_correct(mesh_runs):
    out = mesh_runs["sound"]
    assert out["chips"] == 4
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("variant", ["control", "unchanged", "half",
                                     "altered"])
def test_mesh_control_and_faults_are_seen(mesh_runs, variant):
    out = mesh_runs[variant]
    assert not out["correct"], out["checks"]


def _two_chips():
    """Times in ns, window [0, 1000]. Chip 0 runs a synchronous all-reduce
    and an asynchronous all-gather pair; chip 1 an async all-reduce pair
    whose done names its start, beside another start it must not take."""
    from lib import trace as tr
    t = tr.Trace()
    t.device_ops = {
        "/device:TPU:0": [
            ("%fusion.1 = f32[2] fusion(f32[2] %p)", 0, 100),
            ("%all-reduce.9 = (f32[10], f32[]) all-reduce(f32[10] %a, "
             "f32[] %b), to_apply=%add", 100, 160),
            ("%all-gather-start = (f32[5,85], f32[20,85]) "
             "all-gather-start(f32[5,85] %l)", 300, 310),
            ("%estep_stats_pallas.3 = f32[1,128] custom-call()", 310, 380),
            ("%all-gather-done = f32[20,85] all-gather-done((f32[5,85], "
             "f32[20,85]) %all-gather-start)", 390, 400)],
        "/device:TPU:1": [
            ("%all-reduce-start.2 = f32[8] all-reduce-start(f32[8] %x)",
             500, 510),
            ("%all-reduce-start.1 = f32[4] all-reduce-start(f32[4] %y)",
             520, 530),
            ("%all-reduce-done.1 = f32[4] all-reduce-done(f32[4] "
             "%all-reduce-start.1)", 600, 620),
            ("%all-reduce-scatter-fusion = f32[2] fusion(f32[8] %z)",
             700, 900)],
        "/device:TPU:2": [("%fusion.4 = f32[2] fusion(f32[2] %p)", 0, 50)],
    }
    return t


def test_collective_ms_sums_per_chip_and_averages_over_chips():
    layer = {"trace": _two_chips(), "lo": 0, "hi": 1000,
             "fits": [{}, {}]}
    value = registry.metric_reader("collective_ms.fit").read(layer)
    # chip 0: 60 ns sync + 100 ns from the gather's start to its done;
    # chip 1: 100 ns from start.1 to done.1; chip 2 ran none
    assert value == pytest.approx((160 + 100) / 2 / 1e6 / 2)


def test_collective_ms_is_silent_without_collectives():
    t = _two_chips()
    del t.device_ops["/device:TPU:0"], t.device_ops["/device:TPU:1"]
    layer = {"trace": t, "lo": 0, "hi": 1000, "fits": [{}]}
    assert registry.metric_reader("collective_ms.fit").read(layer) is None
