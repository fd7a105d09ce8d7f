"""Order statistics of the benchmark, in one place."""

from __future__ import annotations

import numpy as np


def quantile(values, q: float):
    """The ``q`` quantile, linear between order statistics; None when
    there is nothing to read."""
    v = [x for x in values if x is not None]
    if not v:
        return None
    return float(np.quantile(np.asarray(v, np.float64), q))
