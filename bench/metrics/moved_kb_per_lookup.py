"""moved_kb_per_lookup (fan-out probes): `shard.index.bytes_moved` (the
probed rows brought to the trapdoors' chip from the others) counted in
the traced window, in KB (1,024 bytes), over the lookups answered by
its end; None where the program keeps no such counter."""


def read(ctx):
    ops = len(ctx.window.answered_by_end())
    moved = ctx.counters.get("shard.index.bytes_moved")
    if not ops or moved is None:
        return None
    return moved / 1024 / ops
