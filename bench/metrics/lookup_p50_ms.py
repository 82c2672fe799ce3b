"""lookup_p50_ms: median latency over every lookup due in the window,
from when it was due (`driver.latency_ms`), in the lookup cells."""
from harness.driver import latency_ms, nearest_rank


def read(ctx):
    w = ctx.window
    return nearest_rank([latency_ms(r, w.close)
                         for r in w.window_records()], 50)
