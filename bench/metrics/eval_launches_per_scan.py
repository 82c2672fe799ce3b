"""eval_launches_per_scan (fused scan): `eval.launches` counted in the
traced window over the range scans answered by its end."""


def read(ctx):
    scans = sum(r["op"] == "range" for r in ctx.window.answered_by_end())
    launches = ctx.counters.get("eval.launches")
    if not scans or launches is None:
        return None
    return launches / scans
