"""Order statistics the benchmark reports: nearest-rank percentiles and
the quartile spread used to size bounds.

``percentile`` is copied from the repository's CPU load generator
(``benchmarks/serving_load.py``) so both read a tail the same way.
"""

from __future__ import annotations

import statistics
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of ``xs`` (``q`` in 0..100).

    Raises on an empty sample: a tail of nothing is not 0."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    s = sorted(xs)
    k = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return float(s[k])


def quartile_spread(xs: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with Python's ``statistics.quantiles`` quartiles."""
    q1, med, q3 = statistics.quantiles(list(xs), n=4)
    return (q3 - q1) / med
