"""Key chooser `uniform_rows`: the value of a uniformly drawn row."""


def draw(entry, keys):
    return (int(keys.values[keys.rng.integers(len(keys.values))]),)
