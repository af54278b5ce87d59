"""The work the fused E-step kernel (``estep_stats``) has to do.

Counted from the algorithm's own shapes: N rows of nonzero weight, d
features and K components, unpadded. Whatever implements the kernel (a
128-lane padded copy, K padded to the MXU width, several passes of a
low-precision product) does the same work by this count, so removing
padding or passes raises the roofline share and leaves the count alone.

Per row and component: the log density by the matmul identity is 2·d
multiply-adds (x² against -1/(2 var), x against mu/var), 4·d flops; the
statistics s1 = rᵀx and s2 = rᵀx² are 2·d flops each. The exp and
softmax are O(K) per row and not counted. Bytes: each row of x (d
float32) and its weight are read once; parameters and outputs are O(K·d).
"""
from __future__ import annotations

STABLE_NAME = "estep_stats_pallas"


def count(rows: int, d: int, k: int) -> tuple[float, float]:
    """(flops, bytes) of one E-step over ``rows`` useful rows."""
    flops = 8.0 * rows * d * k
    bytes_ = 4.0 * (rows * d + rows)
    return flops, bytes_
