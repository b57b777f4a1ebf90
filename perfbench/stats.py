"""The one percentile the benchmark uses: nearest rank, no interpolation, so a
reported tail is a time some request really had."""

import math


def percentile(values, q: float) -> float:
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])
