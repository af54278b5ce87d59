"""Pallas TPU kernels for the EM hot path: parity-tested in interpret mode
on the CPU, compiled for a described v5e in tests/test_tpu_compile.py, and
checked on the chip by chip_smoke.py (DESIGN.md §3/§5)."""
from repro.kernels.ops import estep_stats, gmm_logpdf, kmeans_assign
from repro.kernels import ref

__all__ = ["estep_stats", "gmm_logpdf", "kmeans_assign", "ref"]
