"""Key chooser `absent`: a value of the column's domain that no row
holds, drawn uniformly."""
import numpy as np


def draw(entry, keys):
    absent = keys.cache.get("absent")
    if absent is None:
        lo, hi = keys.data["domain"]
        absent = keys.cache["absent"] = np.setdiff1d(np.arange(lo, hi),
                                                     keys.values)
    return (int(absent[keys.rng.integers(len(absent))]),)
