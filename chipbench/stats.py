"""The spread the bounds in BENCHMARK.json are set from.

Kept apart from the tools so the tests can hold it to hand-made inputs,
and so every PR reduces its sets of runs the same way.
"""

from __future__ import annotations

import statistics
from typing import Optional, Sequence


def quartile_spread(values: Sequence[float]) -> Optional[float]:
    """(Q3 - Q1) / median with `statistics.quantiles(values, n=4)`."""
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else None
