"""A quantile over ALL samples of one series of the window: the median for
q = 0.5, else the nearest-rank quantile (with fewer than 1/(1-q) samples that
is the largest one)."""

import math
import statistics


def read(run: dict, samples: str, q: float):
    xs = sorted(run["samples"][samples])
    if not xs:
        return None
    if q == 0.5:
        return statistics.median(xs)
    return xs[min(len(xs), max(1, math.ceil(q * len(xs)))) - 1]
