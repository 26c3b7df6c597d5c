"""Readings over request latencies. Pure Python + NumPy, no program code."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) as the loadgen engine takes it: the value at
    rank floor(q * n) of the sorted sample (no interpolation, so a reading
    is always a latency that some request had)."""
    vals = np.sort(np.asarray(values, dtype=np.float64))
    if vals.size == 0:
        raise ValueError("percentile of an empty sample")
    return float(vals[min(vals.size - 1, int(q * vals.size))])


def counter_delta(before: dict, after: dict, metric: str, field: str = "value") -> float:
    """How far one field of one metric moved between two snapshots of the
    program's metrics registry (`value` of a counter, `sum` or `count` of
    a histogram); a metric that is not there yet counts from 0."""
    return float((after.get(metric) or {}).get(field) or 0.0) - float(
        (before.get(metric) or {}).get(field) or 0.0
    )
