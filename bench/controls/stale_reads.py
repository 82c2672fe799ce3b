"""Control `stale_reads`: reads see the loaded rows and no write, so
read-your-admitted-writes is broken."""
import numpy as np


def view(base, written):
    return np.asarray(base, np.int64)
