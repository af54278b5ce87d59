"""Find the benchmark's parts by name.

Every configuration, traffic mix, kind of traffic, per-layer metric
reader, kernel count and limit set is a file of its own, named after the
entry of ``BENCHMARK.json`` that uses it. Adding one is adding a file.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class BenchError(Exception):
    """A run cannot be made as asked (no chip, unknown name, bad file)."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one reader or driver file by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"unknown workload {name!r}")


def config(name: str) -> dict:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return load_json(BENCH_DIR / "limits" / f"{workload_name}.json")


def kind(name: str):
    return load_module(BENCH_DIR / "kinds" / f"{name}.py")


def generator(name: str):
    return load_module(BENCH_DIR / "data" / f"{name}.py")


def metric_reader(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


def kernel_count(name: str):
    return load_module(BENCH_DIR / "kernels" / f"{name}.py")


def peaks() -> dict:
    return load_json(BENCH_DIR / "peaks.json")


def peak_for(device_kind: str) -> dict:
    """The peak row of one device kind. An unknown kind is an error: a
    roofline share against a guessed peak is no measurement."""
    table = peaks()["devices"]
    if device_kind not in table:
        raise BenchError(f"no peak for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]
