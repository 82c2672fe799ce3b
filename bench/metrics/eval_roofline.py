"""eval_roofline (eval kernel): the fused scan's eval launches' least
time on the chip (`harness.roofline`: the larger of bytes over the
memory bandwidth and operations over the int8 peak) over their summed
device time in the trace.

Launches and their shapes come from the `executor.eval_tile` spans
(`rows`) and their parent `executor.fused_eval` spans (`atoms`); the
mixes that report this metric scan one column, so each launch reads
one column's tile.  Device time is the summed duration of the launches
of the programs named in PROGRAMS, as the trace names them (the jitted
eval of `repro.db.executor.jitted_dedup_eval`), summed over the chips
as the launches' work is, so the share cannot pass 100% on more chips
either.
"""
from harness import roofline
from harness.trace import kernel_seconds

PROGRAMS = ("jit_fn",)


def read(ctx):
    if not ctx.trace or not ctx.peaks or not ctx.trace_window:
        return None
    by_sid = {s.sid: s for s in ctx.spans}
    least = t_bytes = t_ops = 0.0
    for s in ctx.spans:
        if s.name != "executor.eval_tile":
            continue
        parent = by_sid.get(s.parent_sid)
        if parent is None or parent.name != "executor.fused_eval":
            continue
        cost = roofline.eval_launch_cost(
            atoms=int(parent.args["atoms"]), rows=int(s.args["rows"]),
            **ctx.sizes)
        t = roofline.least_seconds(cost, ctx.peaks)
        least += t["seconds"]
        t_bytes += t["bytes_s"]
        t_ops += t["ops_s"]
    kernel = sum(kernel_seconds(modules, PROGRAMS, *ctx.trace_window)
                 for modules in ctx.trace["modules"])
    if not least or not kernel:
        return None
    ctx.notes.append(
        f"eval roofline: least {least:.6f}s (bytes {t_bytes:.6f}s, ops "
        f"{t_ops:.6f}s: bound by {'bytes' if t_bytes >= t_ops else 'ops'})"
        f" over kernel time {kernel:.6f}s")
    return 100.0 * least / kernel
