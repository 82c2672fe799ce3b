"""queue_wait_p95_ms (ServeLoop), in the read-write cells: 95th
percentile of the point class's `serve.queue_wait_s` histogram over the
traced window, the time from a request's admission to the batch that
drafted it."""
from harness.driver import nearest_rank


def read(ctx):
    xs = ctx.histograms.get("serve.queue_wait_s{klass=point}") or []
    v = nearest_rank(xs, 95)
    return None if v is None else v * 1e3
