"""The control, a step below the configuration's precision (``HIGH``,
three bf16 passes, for ``HIGHEST``), comes out not correct; the program
at its own precision comes out correct. On the CPU, at test sizes, with
the passes written out (``bench/lib/control.py``) so that they read as
on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cells import run_small
from lib import control, registry

@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_dot_3pass_is_coarser_than_float32():
    rng = np.random.default_rng(0)
    a = rng.uniform(0, 1, (256, 84)).astype(np.float32)
    b = rng.uniform(-30, 30, (84, 10)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    err3 = np.abs(np.asarray(control.dot_3pass(a, b)) - exact).max()
    err = np.abs(np.asarray(a @ b, np.float64) - exact).max()
    bf16 = np.abs(np.asarray(jnp.matmul(
        jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16),
        preferred_element_type=jnp.float32), np.float64) - exact).max()
    assert err < err3 < bf16


def test_serving_control_fails_where_the_program_passes(monkeypatch):
    """The cell's own check, with the control's scores put in the place
    of the served ones."""
    kind = registry.kind("serve")
    real = kind.err_share
    seen = {}

    def both(rows, served, params):
        seen["program"] = real(rows, served, params)
        seen["control"] = real(rows, kind.control_scores(rows, params),
                               params)
        return seen["control"]
    monkeypatch.setattr(kind, "err_share", both)
    monkeypatch.setattr(registry, "kind", lambda name: kind)
    out = run_small(monkeypatch, "wadi.serve.steady")
    limit = out["checks"]["score_err_share"]["limit"]
    assert seen["program"] <= limit < seen["control"]
    assert not out["correct"]


@pytest.mark.parametrize("workload,rows", [("wadi.fedgen", 120000),
                                           ("smd.dem", 708405)])
def test_fit_control_fails(monkeypatch, workload, rows):
    control.lower_precision(monkeypatch.setattr)
    out = run_small(monkeypatch, workload, sizes={"train_rows": rows},
                    seconds=1.0,
                    traffic={"fit_config": {"backend": "reference"}})
    assert not out["correct"], out["checks"]
