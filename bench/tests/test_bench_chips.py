"""A cell that asks for more chips than the test process has devices:
a copy of hg38-point with `"chips": 4`, written into a `BENCHMARK.json`
of its own, runs its tiny copy through `test_bench_runs`'s path in a
child process on 4 virtual CPU devices, untraced and traced, comes out
correct, and its control is refused."""
import json
import shutil

import jax
import pytest

from harness import device, spec
from test_bench_runs import _assert_correct, _assert_line, _assert_refused
from test_bench_runs import _run
from tiny import OTHER_ROWS, TINY_ROWS, tiny_config

NAME = "hg38-point"
CHIPS = 4


@pytest.fixture(scope="module")
def four_chip_root(tmp_path_factory):
    """A checkout's `BENCHMARK.json` in which hg38-point asks for four
    chips (built by the same `one_chip` builder), with its configuration
    file."""
    root = tmp_path_factory.mktemp("four_chip")
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        if w["name"] == NAME:
            w["chips"] = CHIPS
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = spec.workload(bench, NAME)["config"]
    file = next(c["file"] for c in bench["configs"] if c["name"] == cfg)
    (root / file).parent.mkdir(parents=True)
    shutil.copy(spec.ROOT / file, root / file)
    return root


def test_this_process_is_short_of_chips():
    """The copy below runs in a child only because this process has too
    few devices, and a chip run short of chips is still refused."""
    assert len(jax.devices()) < CHIPS
    with pytest.raises(device.NoDevice, match=f"{CHIPS} chips asked"):
        device.check(CHIPS, require_tpu=False)


def test_four_chip_copy_is_correct(four_chip_root):
    out = _run(NAME, root=four_chip_root)
    _assert_correct(out)
    assert out["device"]["count"] == CHIPS


def test_four_chip_copy_traced(four_chip_root, tmp_path):
    out = _run(NAME, traced=True, root=four_chip_root,
               trace_dir=tmp_path / "trace")
    _assert_line(out, traced=True)
    assert out["correct"], out["checks"]
    dev = out["device"]
    assert dev["count"] == CHIPS
    # CPU time is no device time: the CPU client's events stay one pool
    assert dev["busy_s_by_chip"] == [dev["busy_s"]]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert {"eval_launches_per_lookup", "device_idle_pct.lookup",
            "probe_idle_pct.lookup"} <= set(out["metrics"])
    gaps = sum(v for _, v in out["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx(dev["window_s"] - dev["busy_s"])


def test_four_chip_control_is_refused(four_chip_root):
    out = _run(NAME, control=True, root=four_chip_root)
    _assert_refused(out)
    assert out["device"]["count"] == CHIPS


def test_tiny_config_sizes_a_table_it_does_not_know():
    cfg = spec.config(spec.load_benchmark(), "hg38-bfv")
    cfg["tables"].append({**cfg["tables"][1], "name": "hg38_copy"})
    cfg["tables"].append({"name": "orders", "column": "key",
                          "recordcount": 10 ** 6, "key_bits": 34})
    tables = {t["name"]: t for t in tiny_config(cfg)["tables"]}
    assert tables["hg38_copy"]["rows"] == OTHER_ROWS
    assert tables["orders"]["recordcount"] == OTHER_ROWS
    assert tables["hg38"]["rows"] == TINY_ROWS["hg38"]
    assert tables["hg38_prefix"]["rows"] == TINY_ROWS["hg38_prefix"]
