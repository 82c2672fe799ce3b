"""device_idle_pct.lookup: share of the traced window in which no
operation ran on the device (`harness.trace.busy_seconds`), in the
lookup cells."""
from harness.idle import idle_pct


def read(ctx):
    return idle_pct(ctx)
