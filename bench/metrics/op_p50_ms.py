"""op_p50_ms: median latency over every request due in the window,
reads and inserts alike, from when it was due (`driver.latency_ms`), in
the read-write cells."""
from harness.driver import latency_ms, nearest_rank


def read(ctx):
    w = ctx.window
    return nearest_rank([latency_ms(r, w.close)
                         for r in w.window_records()], 50)
