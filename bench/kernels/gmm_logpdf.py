"""The work the per-component log density kernel (``gmm_logpdf``) has to do.

Counted from the algorithm's own shapes: N rows that hold a request's
data (not the slab's padding), d features and K components, unpadded.
Per row and component the matmul identity is 2·d multiply-adds, 4·d
flops. Bytes: each row of x (d float32) is read once and each of its K
log densities written once.
"""
from __future__ import annotations

STABLE_NAME = "gmm_logpdf_pallas"


def count(rows: int, d: int, k: int) -> tuple[float, float]:
    """(flops, bytes) of the log densities of ``rows`` useful rows."""
    flops = 4.0 * rows * d * k
    bytes_ = 4.0 * (rows * d + rows * k)
    return flops, bytes_
