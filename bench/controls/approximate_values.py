"""Control `approximate_values`: reads see every value with its lowest
bit dropped, an approximate answer where it was exact."""
import numpy as np


def view(base, written):
    rows = np.concatenate([np.asarray(base, np.int64),
                           np.asarray(written, np.int64)])
    return rows >> 1 << 1
