"""Small statistics shared by the harness and its readers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile of all values (linear interpolation)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def mean(values) -> float:
    return float(np.mean(np.asarray(values, np.float64)))
