"""Builder `sharded`: every table of the mix split into its entry's
`shards` contiguous shards (`ShardedTable`), one a chip of the
configuration's `chips`, each with the `ShardedIndex` its entry asks
for, behind one `ServeLoop` of `ShardedQueryServer`s, with the mix's
`batch` and, where the mix states it, its `tenant_queue_cap`.

The layout is checked, never assumed: a process with fewer devices than
`chips` is refused before any mesh is built (`launch.mesh` would build
a smaller one), and so is a mesh, column or shard index that does not
lie on `chips` distinct devices.  Trapdoors arrive on the default
device, the home chip of every fan-out eval.

Phases: `keygen`; `ingest.<table>` and `index_build.<table>` for every
table the mix loads; the shared `trapdoor_pool`; `warmup` (the mix's
warm-up batches through the served path).  Keys come from the
configuration's `key_seed`, as in `one_chip`.  Serves reads only.
"""
import time

from harness.deploy import raw_key, serve, trapdoor_pool
from harness.meter import phase
from harness.targets import ProgramTarget
from harness.traffic import op_module


class ShortOfChips(RuntimeError):
    """The devices at hand cannot hold one shard a chip."""


def build(cfg, mix, data, schedule, seed, record, log, peak):
    import jax

    from repro.core.keys import keygen
    from repro.core.params import make_params
    from repro.db.serve_loop import AdmissionPolicy, ServeLoop

    chips = int(cfg["chips"])
    if len(jax.devices()) < chips:
        raise ShortOfChips(f"{cfg['name']} asks for {chips} chips, this "
                           f"process has {len(jax.devices())} devices")
    if any(op_module(r.op).WRITES for r in schedule.requests):
        raise ValueError("the sharded builder serves reads only")
    params = make_params(cfg["profile"], mode=cfg["cek_mode"])
    with phase("keygen", record, log, peak):
        ks = keygen(params, jax.random.PRNGKey(int(cfg["key_seed"])))
        jax.block_until_ready(ks.cek_rev)

    batch = int(mix["batch"])
    policy = AdmissionPolicy()
    if "tenant_queue_cap" in mix:
        policy = AdmissionPolicy(tenant_queue_cap=int(mix["tenant_queue_cap"]))
    loop = ServeLoop(batch=batch, policy=policy, clock=time.perf_counter)
    for i, t in enumerate(cfg["tables"]):
        if t["name"] in mix["tables"]:
            _serve_table(ks, loop, t, data[t["name"]]["values"],
                         raw_key(seed, 1, i), chips, batch, record, log,
                         peak)

    trapdoors, write_keys = trapdoor_pool(ks, params, schedule, seed,
                                          record, log, peak)
    target = ProgramTarget(loop, schedule.streams, trapdoors, write_keys)
    with phase("warmup", record, log, peak):
        for group in schedule.warm:
            serve(target, group, log)
    shapes = sorted({s[1:] for s in loop.batch_shapes})
    log(f"warm-up drafted batches (class, size): {shapes}")
    return target


def _ids(arr) -> list:
    return sorted(d.id for d in arr.devices())


def _serve_table(ks, loop, entry, values, key, chips, batch, record, log,
                 peak):
    """Ingest one table in shards (and its index), check where each part
    lives, and register its server."""
    import jax

    from repro.db.shard import ShardedIndex, ShardedQueryServer, ShardSpec
    from repro.db.shard.table import ShardedTable

    name, col = entry["name"], entry["column"]
    spec = ShardSpec.create(int(entry["shards"]))
    mesh = sorted({d.id for d in spec.mesh.devices.flat})
    if len(mesh) != chips:
        raise ShortOfChips(f"table {name}: mesh over devices {mesh}, "
                           f"want {chips} distinct")
    with phase(f"ingest.{name}", record, log, peak):
        table = ShardedTable.from_arrays(ks, name, {col: values}, key,
                                         spec=spec)
        jax.block_until_ready(table.columns[col])
    if _ids(table.columns[col].c0) != mesh:
        raise ShortOfChips(f"table {name}: column on devices "
                           f"{_ids(table.columns[col].c0)}, want {mesh}")
    indexes = {}
    if entry.get("index"):
        with phase(f"index_build.{name}", record, log, peak):
            indexes[col] = ShardedIndex.build(ks, table, col)
            jax.block_until_ready([ix.sorted_ct for ix in
                                   indexes[col].shards])
        on = [_ids(ix.sorted_ct.c0) for ix in indexes[col].shards]
        if sorted({i for ids in on for i in ids}) != mesh or any(
                len(ids) != 1 for ids in on):
            raise ShortOfChips(f"table {name}: shard indexes on devices "
                               f"{on}, want one each of {mesh}")
    log(f"table {name}: {table.n_rows} rows in {table.num_shards} shards "
        f"of {table.n_padded_per_shard} padded rows on devices {mesh}, "
        f"{table.ciphertext_bytes()} ciphertext bytes"
        + (f", indexed on {col}" if indexes else ""))
    loop.register(name, ShardedQueryServer(ks, table, batch=batch,
                                           indexes=indexes))
