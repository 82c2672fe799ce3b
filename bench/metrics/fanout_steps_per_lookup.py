"""fanout_steps_per_lookup (fan-out probes): `shard.index.step` spans
opened in the traced window over the lookups answered by its end; None
where the program opens no such span."""


def read(ctx):
    ops = len(ctx.window.answered_by_end())
    steps = sum(s.name == "shard.index.step" for s in ctx.spans)
    if not ops or not steps:
        return None
    return steps / ops
