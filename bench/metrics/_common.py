"""Shared arithmetic of the metric readers."""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    v = list(values)
    return float(np.percentile(v, q)) if v else None
