"""Exact dot product of two float32 vectors, on the host.

A product of two float32 numbers is exact in float64, so only the sum
rounds: in float64 over chunks, the chunk sums added by ``math.fsum``.
Its error is some 1e-15 of the sum of |a_i b_i| at 2**27 terms, far below
what a float32 kernel can reach.
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np

CHUNK = 1 << 20


def exact_dot(a: np.ndarray, b: np.ndarray) -> Tuple[float, float]:
    """(sum of a_i b_i, sum of |a_i b_i|)."""
    parts, mags = [], []
    for i in range(0, a.shape[0], CHUNK):
        p = a[i:i + CHUNK].astype(np.float64) * b[i:i + CHUNK]
        parts.append(float(np.sum(p)))
        mags.append(float(np.sum(np.abs(p))))
    return math.fsum(parts), math.fsum(mags)
