"""The sorted index's storage form: every producer of a `SortedIndex`
stores its sorted copy as int32, and searches over it are exact.

Producers: `SortedIndex.build`, `Table.delta_index`, `merge_index_runs`
(compaction) and `ShardedIndex.build`.  Each run's search positions are
checked against numpy's `searchsorted` over the run's plaintexts, its
row ids against the plaintext matches, and its positions against the
same search over an int64 copy of the run.  `ShardedIndex.build` runs
on 4 virtual CPU devices, one shard a device: JAX fixes its device
count when it starts, so that case runs in a child process (this file
run as a script) that prints what it saw as one JSON line.
"""
import copy
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
GRID = 0.25        # ckks float grid (>> test-ckks equality tolerance)
PROFILES = ("test-bfv", "test-ckks")
PRODUCERS = ("build", "delta_index", "merge_index_runs", "sharded_build")
SHARDS = 4


def _scheme_vals(ks, ints) -> np.ndarray:
    ints = np.asarray(ints)
    if ks.params.profile.scheme == "ckks":
        return ints.astype(np.float64) * GRID
    return ints.astype(np.int64)


def _lanes(ks, probes, seed):
    """Two boundary lanes a probe (lower, upper), encrypted."""
    import jax
    import jax.numpy as jnp
    from repro.core import encrypt as E
    values = E.encrypt(ks, jnp.asarray(np.repeat(probes, 2)),
                       jax.random.PRNGKey(seed))
    return values, np.tile([False, True], len(probes))


def _int64_twin(ix):
    """The same sorted run with its copy held as int64."""
    from repro.db.table import widen
    twin = copy.copy(ix)
    twin.sorted_ct = widen(ix.sorted_ct)
    return twin


def _expect(run_vals, perm, probes):
    """numpy's positions ([B] lanes) and whether `perm` orders the run."""
    ordered = np.sort(run_vals)
    want = np.stack([np.searchsorted(ordered, probes, side="left"),
                     np.searchsorted(ordered, probes, side="right")],
                    axis=1).ravel()
    return want, bool(np.array_equal(run_vals[perm], ordered))


def _rows_exact(run_vals, perm, pos, probes) -> bool:
    """Each probe's row ids (perm[lower:upper]) are its plaintext matches."""
    return all(
        sorted(perm[pos[2 * i]:pos[2 * i + 1]].tolist())
        == np.nonzero(run_vals == p)[0].tolist()
        for i, p in enumerate(probes))


def _report(ks, runs, search, twin_search, probes, seed) -> dict:
    """What a producer's runs show: dtypes, positions and row ids.

    runs: [(SortedIndex, plaintexts in its row-id space)];
    search / twin_search: lanes -> [len(runs), B] positions, over the
    stored runs and over their int64 twins."""
    values, strict = _lanes(ks, probes, seed)
    pos = np.asarray(search(values, strict)).reshape(len(runs), -1)
    pos64 = np.asarray(twin_search(values, strict)).reshape(len(runs), -1)
    dtypes, want, sorted_ok, rows_ok = set(), [], True, True
    for (ix, run_vals), p in zip(runs, pos):
        dtypes |= {str(ix.sorted_ct.c0.dtype), str(ix.sorted_ct.c1.dtype)}
        w, ok = _expect(run_vals, ix.perm, probes)
        want.append(w.tolist())
        sorted_ok &= ok
        rows_ok &= _rows_exact(run_vals, ix.perm, p, probes)
    return {"dtypes": sorted(dtypes), "pos": pos.tolist(),
            "want": want, "pos64": pos64.tolist(),
            "sorted": bool(sorted_ok), "rows": bool(rows_ok)}


def _one_run(ks, ix, run_vals, probes, seed) -> dict:
    twin = _int64_twin(ix)
    return _report(ks, [(ix, run_vals)],
                   lambda v, s: ix.search(ks, v, s),
                   lambda v, s: twin.search(ks, v, s), probes, seed)


def _data(ks, seed):
    """Base and inserted plaintexts with duplicates, and probes: present
    values and absent ones, inside and beyond the column's range."""
    rng = np.random.default_rng(seed)
    base = _scheme_vals(ks, rng.integers(0, 40, 37))
    new = _scheme_vals(ks, rng.integers(0, 40, 11))
    absent = _scheme_vals(ks, [41, 60])
    probes = np.concatenate([rng.choice(base, 3), rng.choice(new, 2),
                             absent])
    return base, new, probes


def _table(ks, base, seed):
    import jax
    from repro.db.table import Table
    return Table.from_arrays(ks, "t", {"v": base}, jax.random.PRNGKey(seed))


def _produce(ks, producer: str) -> dict:
    """One in-process producer's report."""
    import jax
    from repro.db.delta import merge_index_runs
    from repro.db.index import SortedIndex

    base, new, probes = _data(ks, 17)
    t = _table(ks, base, 5)
    if producer == "build":
        return _one_run(ks, SortedIndex.build(ks, t, "v"), base, probes, 9)
    # two inserts: the second grows the delta run by concatenation
    t.insert(ks, {"v": new[:6]}, jax.random.PRNGKey(6))
    t.insert(ks, {"v": new[6:]}, jax.random.PRNGKey(7))
    didx = t.delta_index(ks, "v")
    if producer == "delta_index":
        return _one_run(ks, didx, new, probes, 9)
    assert producer == "merge_index_runs"
    merged, compares = merge_index_runs(
        ks, SortedIndex.build(ks, t, "v"), didx, id_offset=t.n_rows)
    assert compares > 0
    return _one_run(ks, merged, np.concatenate([base, new]), probes, 9)


def _produce_sharded(profile: str) -> dict:
    """`ShardedIndex.build` over a 4-shard table on a mesh of the 4
    devices, searched by the fan-out search."""
    import jax
    from conftest import get_scheme_ks
    from repro.db.shard import ShardedIndex, ShardSpec
    from repro.db.shard.table import ShardedTable

    ks = get_scheme_ks(profile)
    base, _, probes = _data(ks, 17)
    st = ShardedTable.from_arrays(ks, "t", {"v": base},
                                  jax.random.PRNGKey(5),
                                  spec=ShardSpec.create(SHARDS,
                                                        use_mesh=True))
    sidx = ShardedIndex.build(ks, st, "v")
    twin = ShardedIndex("v", [_int64_twin(ix) for ix in sidx.shards])
    runs = list(zip(sidx.shards, np.split(base, st.offsets[1:-1])))
    out = _report(ks, runs, lambda v, s: sidx.search(ks, v, s),
                  lambda v, s: twin.search(ks, v, s), probes, 9)
    out["devices"] = sorted({d.id for ix in sidx.shards
                             for d in ix.sorted_ct.c0.devices()})
    return out


@pytest.fixture(scope="module")
def sharded_child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"), XLA_FLAGS=(
                   os.environ.get("XLA_FLAGS", "") +
                   f" --xla_force_host_platform_device_count={SHARDS}"
               ).strip())
    p = subprocess.run([sys.executable, __file__], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("producer", PRODUCERS)
def test_index_stores_int32_and_searches_exactly(scheme_ks, producer,
                                                 request):
    if producer == "sharded_build":
        out = request.getfixturevalue("sharded_child")[
            scheme_ks.params.profile.name]
        assert out["devices"] == list(range(SHARDS))
    else:
        out = _produce(scheme_ks, producer)
    assert out["dtypes"] == ["int32"]
    assert out["sorted"]
    assert out["pos"] == out["want"]
    assert out["pos64"] == out["pos"]
    assert out["rows"]


if __name__ == "__main__":
    sys.path.insert(0, str(REPO / "tests"))
    print(json.dumps({p: _produce_sharded(p) for p in PROFILES}))
