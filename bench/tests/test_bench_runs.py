"""Whole runs of each cell on the CPU at test-bfv with a few hundred
rows (the harness's look for a chip skipped): the program comes out
correct, the control and each planted fault of the timed path come out
not correct, and the entry point refuses a machine with no TPU.  A cell
that asks for more chips than this process has devices runs in a child
process on as many virtual CPU devices (`tiny.run`)."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tiny
from harness import cell, spec
from tiny import tiny_config, tiny_mix

BENCH = spec.load_benchmark()
ROOT = spec.ROOT
SEED = 2**31 + 1234


def _run(name, *, seconds=1.0, traced=False, control=False, rate=40.0,
         root=ROOT, trace_dir=None):
    return tiny.run(name, SEED, seconds, traced, control=control, rate=rate,
                    root=root, trace_dir=trace_dir)


def _assert_line(out, traced):
    assert list(out)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    names = set(out["metrics"])
    assert (names <= e2e) != traced
    json.dumps(out)


def _assert_correct(out):
    _assert_line(out, traced=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]


def _assert_refused(out):
    assert not out["correct"]
    assert max(c["value"] for c in out["checks"].values()) > 0


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_program_is_correct(name):
    _assert_correct(_run(name))


def test_traced_run_reports_layers():
    out = _run("hg38-point", traced=True)
    _assert_line(out, traced=True)
    assert out["correct"]
    assert out["device"]["busy_s"] > 0
    assert {"eval_launches_per_lookup", "queue_wait_p95_ms.lookup",
            "device_idle_pct.lookup"} <= set(out["metrics"])
    assert out["breakdown"]["idle_gaps"]


def test_two_streams_in_one_run():
    """A mix of an open and a closed loop on two tables runs from data
    alone: hg38-point's lookups beside one hg38-scan client."""
    point = spec.traffic("hg38-point")
    scan = spec.traffic("hg38-scan")
    mix = tiny_mix({**point, "tables": ["hg38_prefix", "hg38"],
                    "streams": point["streams"] + [
                        {**scan["streams"][0], "clients": 1}]}, rate=10.0)
    wl = spec.workload(BENCH, "hg38-point")
    out = cell.run("hg38-point", SEED, 1.0, False, require_tpu=False,
                   config_override=tiny_config(spec.config(
                       BENCH, wl["config"])),
                   mix_override=mix, log=lambda line: None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 10


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_control_is_refused(name):
    _assert_refused(_run(name, control=True))


def _flip_first(mask_fn):
    def wrapped(*a, **k):
        m = np.array(mask_fn(*a, **k))
        m[0] = ~m[0]
        return m
    return wrapped


def test_fault_scan_answer_altered(monkeypatch):
    import repro.db.executor as X
    monkeypatch.setattr(X, "scan_leaf_mask", _flip_first(X.scan_leaf_mask))
    assert not _run("hg38-scan")["correct"]


def test_fault_probe_answer_altered(monkeypatch):
    from repro.db.index import SortedIndex
    search = SortedIndex.search

    def shifted(self, *a, **k):
        pos = search(self, *a, **k)
        return np.minimum(pos + 1, self.n_rows)
    monkeypatch.setattr(SortedIndex, "search", shifted)
    assert not _run("hg38-point")["correct"]


def test_fault_half_the_batch_left_out(monkeypatch):
    from repro.db.query_serve import QueryServer
    run = QueryServer.run

    def half(self):
        res = run(self)
        keep = sorted(res)[:len(res) // 2]
        return {k: res[k] for k in keep}
    monkeypatch.setattr(QueryServer, "run", half)
    out = _run("hg38-point")
    assert not out["correct"]
    assert out["checks"]["missing_answers"]["value"] > 0


def test_fault_insert_leaves_state_unchanged(monkeypatch):
    from repro.db.table import Table

    def dropped(self, ks, data, key):
        n = len(next(iter(data.values())))
        return self.n_total + np.arange(n, dtype=np.int64)
    monkeypatch.setattr(Table, "insert", dropped)
    out = _run("ycsb-d-latest", seconds=2.0)
    assert not out["correct"]
    assert out["checks"]["missing_writes"]["value"] > 0


def _entry(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",)}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hg38-point",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_refuses_a_machine_without_tpu():
    p = _entry(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_entry_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _entry(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
