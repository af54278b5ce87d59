"""Each fault a cell can have, planted under the timed path
(``bench/lib/faults.py``), makes the run come out not correct; the same
run without it comes out correct. On the CPU, at test sizes."""
import jax
import pytest

from cells import run_small
from lib import faults

FIT_SIZES = {"train_rows": 120000}
FIT_CELLS = {"wadi.fedgen": "fedgen", "smd.dem": "dem"}


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", ["wadi.serve.steady", "wadi.serve.flood"])
def test_sound_serving_run_is_correct(monkeypatch, workload):
    out = run_small(monkeypatch, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.SERVE))
def test_serving_fault_is_seen(monkeypatch, fault):
    faults.SERVE[fault](monkeypatch.setattr)
    # the flood keeps every slot busy, so every slot's answers are checked
    out = run_small(monkeypatch, "wadi.serve.flood")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", sorted(FIT_CELLS))
def test_sound_fit_run_is_correct(monkeypatch, workload):
    out = run_small(monkeypatch, workload, sizes=FIT_SIZES, seconds=1.0)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FIT))
@pytest.mark.parametrize("workload", sorted(FIT_CELLS))
def test_fit_fault_is_seen(monkeypatch, workload, fault):
    faults.FIT[fault](monkeypatch.setattr, FIT_CELLS[workload])
    out = run_small(monkeypatch, workload, sizes=FIT_SIZES, seconds=1.0)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.MERGE))
def test_merge_fault_is_seen(monkeypatch, fault):
    """A fault in FedGenGMM's merge leaves every fit a fixed point of EM
    on the rows it was fit to; the synthetic set's own check sees it."""
    faults.MERGE[fault](monkeypatch.setattr)
    out = run_small(monkeypatch, "wadi.fedgen", sizes=FIT_SIZES, seconds=1.0)
    checks = out["checks"]
    assert checks["synthetic_misfit"]["value"] > \
        checks["synthetic_misfit"]["limit"], checks
    assert not out["correct"]
