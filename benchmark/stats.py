"""Arithmetic shared by the drivers and the per-layer readers."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics: position q/100 * (n - 1) in the sorted list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def with_failures(samples: Sequence[Optional[float]],
                  penalty: float) -> List[float]:
    """A request that was refused, failed or never finished has no sample
    (None). It enters every tail at `penalty`, the length of the whole run,
    so that it misses any limit a user could set."""
    return [penalty if s is None else s for s in samples]
