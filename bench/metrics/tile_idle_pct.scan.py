"""tile_idle_pct.scan (fused scan), in the scan cells: share of the
traced window in which the device is idle while the innermost program
span is the fused scan's (`executor.fused_eval`, `executor.eval_tile`;
`harness.spans`)."""
from harness.spans import idle_share

SPANS = ("executor.fused_eval", "executor.eval_tile")


def read(ctx):
    return idle_share(ctx, SPANS)
