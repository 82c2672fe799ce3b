"""Op `range`: Range(column, enc(lo), enc(hi)), the ids of the rows
with lo <= value <= hi.  Its bounds go out as client-side trapdoors."""
import numpy as np

CLIENT_ENCRYPTS = True
WRITES = False


def submit(via, req):
    from repro.db import plan as P
    lo, hi = via.trapdoors
    return via.loop.submit(via.tenant, via.table, P.Range(via.column, lo, hi))


def answer(values, rows):
    lo, hi = values
    return np.nonzero((rows >= lo) & (rows <= hi))[0].astype(np.int64)
