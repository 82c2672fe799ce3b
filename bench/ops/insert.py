"""Op `insert`: one row holding v, through `ServeLoop.submit_insert`
(the server encrypts it under the request's key).  Its answer is the
row's global id, the rows before it (loaded and inserted) counted; once
acknowledged, a read of v after the window has to return that id."""
import numpy as np

CLIENT_ENCRYPTS = False
WRITES = True


def submit(via, req):
    return via.loop.submit_insert(
        via.tenant, via.table, {via.column: np.asarray(req.values, np.int64)},
        via.key)


def answer(values, rows):
    return np.asarray([len(rows)], np.int64)


def apply(values, written):
    """The reference's table after the insert."""
    written.append(values[0])


def readback(values):
    """The read, sent after the window, that has to find the row."""
    return "eq", tuple(values)
