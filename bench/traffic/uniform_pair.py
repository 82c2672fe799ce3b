"""Key chooser `uniform_pair`: two values of uniformly drawn rows,
lo < hi (a range's bounds)."""
import numpy as np


def draw(entry, keys):
    while True:
        lo, hi = np.sort(keys.values[keys.rng.integers(len(keys.values),
                                                       size=2)])
        if lo < hi:
            return int(lo), int(hi)
