"""eval_launches_per_op (index probes): `eval.launches` counted in the
traced window over the requests answered by its end."""


def read(ctx):
    ops = len(ctx.window.answered_by_end())
    launches = ctx.counters.get("eval.launches")
    if not ops or launches is None:
        return None
    return launches / ops
