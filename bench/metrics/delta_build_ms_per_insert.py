"""delta_build_ms_per_insert (write path): summed time of the
`delta.index_build` spans that built a fresh delta-run index, over the
inserts answered by the traced window's end."""


def read(ctx):
    inserts = sum(r["op"] == "insert"
                  for r in ctx.window.answered_by_end())
    if not inserts:
        return None
    ms = sum((s.t1 - s.t0) * 1e3 for s in ctx.spans
             if s.name == "delta.index_build" and s.args.get("fresh"))
    return ms / inserts
