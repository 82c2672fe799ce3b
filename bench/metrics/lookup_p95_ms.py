"""lookup_p95_ms: 95th percentile (nearest rank) of the latencies that
lookup_p50_ms takes the median of.  A lookup still unanswered at the
close counts with the time it had waited; one failed or refused counts
as infinitely late."""
from harness.driver import latency_ms, nearest_rank


def read(ctx):
    w = ctx.window
    return nearest_rank([latency_ms(r, w.close)
                         for r in w.window_records()], 95)
