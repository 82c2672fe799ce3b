"""write_idle_pct.op (write path), in the read-write cells: share of
the traced window in which the device is idle while the innermost
program span is the write path's: applying a mutation, appending to the
delta run and encrypting its rows on the server, building the delta
run's index (`harness.spans`)."""
from harness.spans import idle_share

SPANS = ("server.mutation", "table.insert", "table.encrypt",
         "delta.index_build")


def read(ctx):
    return idle_share(ctx, SPANS)
