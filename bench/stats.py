"""Percentiles as the benchmark defines them: numpy's linear
interpolation over every sample, never a median of chunks."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def pct(values: Sequence[float], q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
