"""The device's idle share of a traced window, for the idle metrics."""


def idle_pct(ctx):
    """100 * (1 - busy / window), or None without a trace; `busy` is
    the mean over the cell's chips, so this is the average chip's idle
    share."""
    tr = ctx.trace
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
