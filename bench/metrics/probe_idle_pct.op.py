"""probe_idle_pct.op (index probes), in the read-write cells: share of
the traced window in which the device is idle while the innermost
program span is an index probe's (`index.search`, `index.step`,
`index.decode`; `harness.spans`)."""
from harness.spans import idle_share

SPANS = ("index.search", "index.step", "index.decode")


def read(ctx):
    return idle_share(ctx, SPANS)
