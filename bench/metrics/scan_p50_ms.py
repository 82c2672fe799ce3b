"""scan_p50_ms: median latency of the range scans answered within the
window, from send to answer (a closed loop sends a client's next scan
when its last one is answered)."""
import statistics


def read(ctx):
    w = ctx.window
    lat = [(r["done"] - r["due"]) * 1e3 for r in w.completed_in_window()
           if r["op"] == "range"]
    return statistics.median(lat) if lat else None
