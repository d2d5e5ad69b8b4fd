"""Percentiles as the benchmark reports them (numpy's linear
interpolation, the same arithmetic as ``serve.slo``'s tracker)."""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile; an infinite sample (a request that never
    got its token) sorts above every finite one."""
    if not len(samples):
        return math.inf
    return float(np.percentile(np.asarray(samples, np.float64), q))
