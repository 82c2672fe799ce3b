"""Key chooser `latest`: YCSB's skewed-latest distribution, the key of
record number max - Zipf(`zipf_constant`) over the records written so
far (the loaded rows, then the inserts in insert order)."""
import numpy as np


def draw(entry, keys):
    top = len(keys.values) + keys.written           # records so far
    back = _zipf(keys, float(entry["zipf_constant"]), top)
    return (keys.value_of(top - 1 - back),)


def _zipf(keys, theta: float, items: int) -> int:
    """Zipf(theta) over 0..items-1: P(i) proportional to 1/(i+1)**theta."""
    cdf = keys.cache.get(("zipf", theta))
    if cdf is None or len(cdf) < items:
        size = max(items, 2 * len(keys.values))
        cdf = np.cumsum(1.0 / np.arange(1, size + 1) ** theta)
        keys.cache[("zipf", theta)] = cdf
    u = keys.rng.random() * cdf[items - 1]
    return int(min(np.searchsorted(cdf, u, side="right"), items - 1))
