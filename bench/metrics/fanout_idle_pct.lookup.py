"""fanout_idle_pct.lookup (fan-out probes), in the sharded lookup cells:
share of the traced window in which the devices are idle while the
innermost program span is the fan-out search's (`shard.index.search`,
`shard.index.step`, `shard.index.decode`; `harness.spans`)."""
from harness.spans import idle_share

SPANS = ("shard.index.search", "shard.index.step", "shard.index.decode")


def read(ctx):
    return idle_share(ctx, SPANS)
