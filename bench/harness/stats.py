"""Small statistics shared by the metric readers."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The q-th percentile (linear interpolation), None for no samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
