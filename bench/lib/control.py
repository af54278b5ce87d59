"""The control: the computation a step below the configuration's precision.

The configurations state float32 with every contraction at
``Precision.HIGHEST``. The step below is ``Precision.HIGH``, three
bfloat16 passes, which is what the control computes on the chip. Off the
chip a float32 product ignores ``HIGH`` and is exact to float32, so
there, and only there, the three passes are written out
(``dot_3pass``), with each operand cut to bfloat16 by truncation; on
these rows that reads somewhat worse than the chip's own ``HIGH``
(``PERF.md`` gives both), so a test on the CPU that sees the control
fail is a test of the check, and the chip's readings set the limits.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

HIGH = jax.lax.Precision.HIGH


def _bf16(v):
    """Keep the top 16 bits of each float32: sign, exponent, 7 bits."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def dot_3pass(a, b):
    """``a @ b`` in three bfloat16 passes: each operand split into a high
    and a low bfloat16 part, and hi·hi + hi·lo + lo·hi summed in float32
    (each product of two bfloat16 values is exact in float32)."""
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    hp = jax.lax.Precision.HIGHEST
    return (jnp.matmul(ah, bh, precision=hp) + jnp.matmul(ah, bl, precision=hp)
            + jnp.matmul(al, bh, precision=hp))


def matmul_high(a, b):
    """``a @ b`` at ``HIGH``: the chip's own passes on a TPU, written out
    elsewhere."""
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=HIGH)
    return dot_3pass(a, b)


def lower_precision(patch) -> None:
    """Run the program's fits a step below its precision: the E- and
    M-step on its plain jnp path (the caller sets ``FitConfig(backend=
    "reference")``) with ``MATMUL_PRECISION`` at ``HIGH``; off the chip,
    every ``HIGH`` product goes through :func:`dot_3pass`. ``patch(owner,
    name, value)`` is ``setattr`` or pytest's ``monkeypatch.setattr``."""
    # by module path: ``repro.core`` exports a function named ``kmeans``
    for m in ("gmm", "em", "kmeans"):
        patch(importlib.import_module(f"repro.core.{m}"), "MATMUL_PRECISION",
              HIGH)
    if jax.default_backend() == "tpu":
        return
    real = jnp.matmul

    def matmul(a, b, *args, precision=None, **kw):
        if precision == HIGH and not args and not kw:
            return dot_3pass(a, b)
        return real(a, b, *args, precision=precision, **kw)
    patch(jnp, "matmul", matmul)
