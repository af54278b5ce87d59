"""The harness refuses to run where it cannot measure."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run
from lib import registry

ROOT = Path(__file__).resolve().parents[2]


def test_cpu_backend_is_refused(capsys):
    rc = run.main(["--workload", "wadi.serve.steady", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == run.EXIT_NO_RUN
    assert out.out == ""
    assert "no TPU" in out.err


def test_too_few_chips_is_refused(monkeypatch):
    class Dev:
        platform = "tpu"
    monkeypatch.setattr("jax.devices", lambda: [Dev()])
    with pytest.raises(registry.BenchError, match="needs 4 chips"):
        run.require_chips(4)


def test_bare_checkout_prints_no_result(tmp_path):
    """With only BENCHMARK.json and the benchmark's own directory there is
    no system to measure: the run fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "wadi.fedgen", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_file_names_only_files_that_exist():
    bench = registry.benchmark()
    for cfg in bench["configs"]:
        assert (ROOT / cfg["file"]).is_file()
        assert registry.config(cfg["name"])["name"] == cfg["name"]
    for wl in bench["workloads"]:
        traffic = registry.traffic(wl["traffic"])
        assert (ROOT / "bench" / "kinds" / f"{traffic['kind']}.py").is_file()
        assert registry.limits(wl["name"])
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]).read)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
