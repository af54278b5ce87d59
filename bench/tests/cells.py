"""Drive a whole benchmark run on the CPU at a test size: the harness's
look for a chip is skipped, the configuration's rows are cut, and the
peak table gets a row for the CPU so that the traced readers run."""
import jax

import run
from lib import registry

SIZES = {"train_rows": 24000, "test_rows": 8000}


class Args:
    def __init__(self, workload, seed=5, seconds=2.0, trace=0):
        self.workload, self.seed = workload, seed
        self.seconds, self.trace = seconds, trace


def run_small(monkeypatch, workload, sizes=None, traffic=None, **kw) -> dict:
    config, traffic_of = registry.config, registry.traffic

    def small_config(name):
        return dict(config(name), **(sizes or SIZES))

    def small_traffic(name):
        t = traffic_of(name)
        if t["kind"] == "serve":
            t.update(size_max=800, check_rows=5000)
        t.update(traffic or {})
        return t

    monkeypatch.setattr(registry, "config", small_config)
    monkeypatch.setattr(registry, "traffic", small_traffic)
    monkeypatch.setattr(registry, "peak_for", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    return run.run(Args(workload, **kw), find_devices=lambda chips:
                   jax.devices())
