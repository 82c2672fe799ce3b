"""Op `eq`: Eq(column, enc(v)), the ids of the rows equal to v.  Its
value goes out as a client-side trapdoor."""
import numpy as np

CLIENT_ENCRYPTS = True
WRITES = False


def submit(via, req):
    from repro.db import plan as P
    return via.loop.submit(via.tenant, via.table,
                           P.Eq(via.column, via.trapdoors[0]))


def answer(values, rows):
    return np.nonzero(rows == values[0])[0].astype(np.int64)
