"""``weighted_segsum``: per-cluster sums of w·x and of w
(``kernels/weighted_segsum``).  The work it needs is one multiply and one
add per element of x, and one add per weight: 2 n d + n FLOPs (not the
one-hot matmul the kernel makes); its least traffic is reading x, w and
the indices once and writing k sums of d and k totals.
"""

from __future__ import annotations


def flops(batch: int, n: int, k: int, d: int, **_) -> float:
    return float(batch * (2 * n * d + n))


def bytes_moved(batch: int, n: int, k: int, d: int, itemsize: int = 4, **_) -> float:
    return float(batch * (itemsize * (n * d + n) + 4 * n + itemsize * (k * d + k)))
