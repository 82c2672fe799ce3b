"""device_idle_pct.op: share of the traced window in which no operation
ran on the device (`harness.trace.busy_seconds`), in the read-write
cells."""
from harness.idle import idle_pct


def read(ctx):
    return idle_pct(ctx)
