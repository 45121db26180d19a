"""Plain reference of the cache's score: exact brute force over every
live row, on the host."""
from __future__ import annotations

import numpy as np


def unit(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def brute_force(queries, q_tenants, keys, k_tenants) -> np.ndarray:
    """Exact best same-tenant score of each query over the live rows:
    float32 products on the host, in blocks of 128 queries."""
    q = unit(queries).astype(np.float32)
    k = unit(keys).astype(np.float32)
    best = np.full(len(q), -np.inf)
    for lo in range(0, len(q), 128):
        s = q[lo:lo + 128] @ k.T
        s = np.where(q_tenants[lo:lo + 128, None] == k_tenants[None, :],
                     s, -np.inf)
        best[lo:lo + 128] = s.max(axis=1)
    return best
