"""Block-shape rules shared by the Pallas kernels.

A TPU block's last two dims must be multiples of the packed (sublane, 128)
tile of its dtype, or span the whole array dim.
"""

from __future__ import annotations

import numpy as np

__all__ = ["sublanes", "tile"]


def sublanes(dtype) -> int:
    """Rows of one packed (8, 128) tile: 8 of 32-bit, 16 of 16-bit."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def tile(n: int, align: int, limit: int) -> int:
    """Largest divisor of ``n`` that is a multiple of ``align`` and at most
    ``limit``; ``n`` itself when it is within ``limit`` or has no such
    divisor."""
    if n <= limit:
        return n
    for t in range(limit - limit % align, 0, -align):
        if n % t == 0:
            return t
    return n
