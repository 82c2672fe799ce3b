"""The four-chip cell hg38-sharded on the CPU: its entries against the
benchmark's rules; its tiny copy on 4 virtual devices, refused with a
fault planted in the child that runs it (`planted.py`) and correct
without one; its traced copy reading the fan-out metrics; and the
`sharded` builder refusing a layout short of chips."""
import jax
import pytest

import planted
import tiny
from harness import deploy, spec, traffic
from test_bench_runs import _assert_correct, _assert_line, _assert_refused

BENCH = spec.load_benchmark()
NAME = "hg38-sharded"
CHIPS = 4
SEED = 2**31 + 5678
FANOUT = {"fanout_idle_pct.lookup", "fanout_steps_per_lookup",
          "moved_kb_per_lookup"}


def test_cell_entries():
    wl = spec.workload(BENCH, NAME)
    assert wl["chips"] == CHIPS
    four = [w for w in BENCH["workloads"] if w["chips"] == CHIPS]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    cfg = spec.config(BENCH, wl["config"])
    assert cfg["deploy"] == "sharded" and cfg["chips"] == CHIPS
    assert cfg["reduced"] == {}
    (table,) = cfg["tables"]
    assert table["rows"] == 34423 and table["index"]
    assert table["shards"] == CHIPS
    mix = spec.traffic(wl["traffic"])
    (st,) = mix["streams"]
    # lookups only: lookup_p*_ms read every request of the window
    assert {e["op"] for e in st["mix"] + st["warm"]} == {"eq"}
    assert mix["tenant_queue_cap"] == 16 * st["rate_per_s"]
    e2e = {m["name"] for m in spec.metrics_for(BENCH, NAME, False)}
    assert e2e == {"setup_s", "lookup_p50_ms", "lookup_p95_ms"}
    layer = spec.metrics_for(BENCH, NAME, True)
    assert {m["name"] for m in layer} == FANOUT
    assert all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("fault", ["fanout_positions_shifted",
                                   "shard_row_ids_offset"])
def test_planted_fault_is_refused(fault):
    out = planted.run(fault, NAME, SEED, 1.0, CHIPS)
    _assert_refused(out)
    assert out["checks"]["wrong_answers"]["value"] > 0
    assert out["device"]["count"] == CHIPS


def test_nothing_planted_is_correct():
    out = planted.run("none", NAME, SEED, 1.0, CHIPS)
    _assert_correct(out)
    assert out["device"]["count"] == CHIPS


def test_traced_copy_reads_the_fanout(tmp_path):
    """75 rows a shard: 7 steps a search at most, each moving the 3
    other shards' 2 lanes a lookup of c0+c1 rows (2 KB each at
    test-bfv) to the home chip."""
    out = tiny.run(NAME, SEED, 1.0, True, trace_dir=tmp_path / "trace")
    _assert_line(out, traced=True)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == FANOUT
    assert 0 < m["fanout_steps_per_lookup"] <= 7
    assert 6 * 3 * 2 * 4 <= m["moved_kb_per_lookup"] <= 7 * 3 * 2 * 4
    assert 0 <= m["fanout_idle_pct.lookup"] <= 100


def _tiny_cell():
    cfg = tiny.tiny_config(spec.config(BENCH, "hg38-sharded-bfv"))
    mix = tiny.tiny_mix(spec.traffic(NAME))
    data = deploy.table_data(cfg, mix["tables"], SEED)
    return cfg, mix, data


def test_builder_refuses_a_process_short_of_chips(monkeypatch):
    from repro.launch import mesh

    def no_mesh(*a, **k):
        raise AssertionError("a mesh was built")
    monkeypatch.setattr(mesh, "make_shard_mesh", no_mesh)
    assert len(jax.devices()) < CHIPS
    builder = spec.load_module("deploys", "sharded")
    cfg, mix, data = _tiny_cell()
    schedule = traffic.make_schedule(mix, data, SEED, 1.0)
    with pytest.raises(builder.ShortOfChips, match="asks for 4 chips"):
        builder.build(cfg, mix, data, schedule, SEED, {}, lambda line: None,
                      lambda: None)


def test_builder_refuses_a_mesh_short_of_chips():
    """Past the device count, a mesh that does not hold one shard a chip
    (here the one device's) is refused before any row is ingested."""
    from repro.db.serve_loop import ServeLoop
    builder = spec.load_module("deploys", "sharded")
    cfg, _, data = _tiny_cell()
    record = {}
    with pytest.raises(builder.ShortOfChips, match="want 4 distinct"):
        builder._serve_table(None, ServeLoop(batch=8), cfg["tables"][0],
                             data["hg38"]["values"], None, CHIPS, 8, record,
                             lambda line: None, lambda: None)
    assert record == {}
