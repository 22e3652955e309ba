"""Percentile arithmetic of the benchmark (nearest rank, no interpolation)."""
from __future__ import annotations

from typing import Sequence


def percentile(vals: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile of `vals` (q in (0, 100])."""
    if not vals:
        raise ValueError("percentile of no values")
    s = sorted(vals)
    rank = max(1, min(len(s), -(-int(q * len(s)) // 100)))
    return float(s[rank - 1])
