"""The fan-out search's spans and its cross-chip byte counter, on 4
virtual CPU devices.

JAX fixes its device count when it starts, so the search runs in a child
process (this file run as a script, with
`--xla_force_host_platform_device_count=4`) that prints what it saw as
one JSON line; the tests here check it.  The child builds a 4-shard
hg38-style table of a few hundred rows at test-bfv twice, on a mesh of
the 4 devices and meshless, and runs one traced fan-out search on each.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
ROWS, SHARDS, LANES = 300, 4, 6


def _search(use_mesh: bool) -> dict:
    """One traced fan-out search over a fresh 4-shard table: its spans,
    counters, the eval calls it made and the bytes the non-home shards'
    gathered rows hold."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.core import encrypt as E
    from repro.core.keys import keygen
    from repro.core.params import make_params
    from repro.db.executor import jitted_eval
    from repro.db.shard import ShardedIndex, ShardSpec
    from repro.db.shard.table import ShardedTable

    ks = keygen(make_params("test-bfv", mode="gadget"),
                jax.random.PRNGKey(3))
    rng = np.random.default_rng(11)
    vals = rng.integers(0, 200, ROWS).astype(np.int64)
    spec = ShardSpec.create(SHARDS, use_mesh=use_mesh)
    st = ShardedTable.from_arrays(ks, "t", {"value": vals},
                                  jax.random.PRNGKey(5), spec=spec)
    sidx = ShardedIndex.build(ks, st, "value")
    probes = np.concatenate([rng.choice(vals, LANES // 2),
                             [201, 230, 256][:LANES - LANES // 2]])
    values = E.encrypt(ks, jnp.asarray(probes), jax.random.PRNGKey(9))
    home = values.c0.devices().pop()
    calls = []
    ev = jitted_eval(ks)

    def counted(rows, vals_):
        calls.append(1)
        return ev(rows, vals_)
    sidx._cmp = counted
    strict = np.zeros(LANES, bool)
    with obs.tracing() as tracer:
        pos = sidx.search(ks, values, strict)
    spans = [{"name": s.name, "sid": s.sid, "parent": s.parent_sid,
              "args": {k: v for k, v in s.args.items()
                       if isinstance(v, (int, str))}}
             for s in tracer.spans]
    counters = {k: v for k, v in obs.REGISTRY.snapshot().items()
                if isinstance(v, int)}
    away = sum(ix.sorted_ct.c0.devices() != {home} for ix in sidx.shards)
    row = sidx.shards[0].sorted_ct.c0[0].nbytes * 2   # a lane's c0 + c1
    want = np.asarray([[np.sum(np.sort(ix_vals) < p) for p in probes]
                       for ix_vals in np.split(vals, st.offsets[1:-1])])
    return {"spans": spans, "counters": counters, "rounds": len(calls),
            "away_shards": int(away), "row_bytes": int(row),
            "devices": sorted({d.id for ix in sidx.shards
                               for d in ix.sorted_ct.c0.devices()}),
            "positions_exact": bool(np.array_equal(pos, want))}


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"), XLA_FLAGS=(
                   os.environ.get("XLA_FLAGS", "") +
                   f" --xla_force_host_platform_device_count={SHARDS}"
               ).strip())
    p = subprocess.run([sys.executable, __file__], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _by_name(out):
    by = {}
    for s in out["spans"]:
        by.setdefault(s["name"], []).append(s)
    return by


@pytest.mark.parametrize("placement", ["mesh", "meshless"])
def test_one_step_and_decode_span_per_round(child, placement):
    out = child[placement]
    assert out["positions_exact"]
    by = _by_name(out)
    (search,) = by["shard.index.search"]
    steps, decodes = by["shard.index.step"], by["shard.index.decode"]
    assert out["rounds"] > 1
    assert len(steps) == len(decodes) == out["rounds"]
    assert search["args"]["steps"] == out["rounds"]
    assert [s["args"]["step"] for s in steps] == list(range(out["rounds"]))
    assert all(s["parent"] == search["sid"] for s in steps)
    step_ids = {s["sid"] for s in steps}
    assert all(d["parent"] in step_ids for d in decodes)
    assert steps[0]["args"]["lanes"] == SHARDS * LANES
    assert out["counters"]["eval.launches"] == out["rounds"]


def test_bytes_moved_counts_the_non_home_shards(child):
    out = child["mesh"]
    assert out["devices"] == list(range(SHARDS))
    assert out["away_shards"] == SHARDS - 1
    want = out["rounds"] * (SHARDS - 1) * LANES * out["row_bytes"]
    assert out["counters"]["shard.index.bytes_moved"] == want > 0


def test_bytes_moved_is_zero_meshless(child):
    out = child["meshless"]
    assert out["devices"] == [0] and out["away_shards"] == 0
    assert out["counters"].get("shard.index.bytes_moved", 0) == 0


if __name__ == "__main__":
    import jax
    jax.config.update("jax_enable_x64", True)
    print(json.dumps({"mesh": _search(True), "meshless": _search(False)}))
