"""Percentile arithmetic of the benchmark: numpy's linear interpolation,
as the engine's own ``control.predictors.percentile`` computes it (copied
so that a change to the program cannot change the yardstick)."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
  """Linear-interpolated ``p``-th percentile (numpy's default method)."""
  xs = sorted(float(v) for v in values)
  if not xs:
    raise ValueError("percentile of no values")
  pos = (len(xs) - 1) * p / 100.0
  lo = math.floor(pos)
  hi = min(lo + 1, len(xs) - 1)
  return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
