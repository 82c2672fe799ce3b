"""The reduction from trace to metrics: busy union, idle share, kernel
time, idle split by host activity, on a small recorded trace kept with
the benchmark, and the eval's operations and bytes from shapes."""
import json
import pathlib

import pytest

from harness import roofline
from harness import trace as T

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_merges_and_clips():
    assert T.union([(0, 2), (1, 3), (5, 6), (7, 9)], 0.5, 8) == [
        (0.5, 3), (5, 6), (7, 8)]


def test_busy_idle_and_kernel_on_hand_events():
    dev = [("a", 1.0, 2.0), ("b", 1.5, 2.5), ("c", 4.0, 5.0)]
    assert T.busy_seconds(dev, 0.0, 10.0) == pytest.approx(2.5)
    assert T.busy_seconds(dev, 2.0, 4.5) == pytest.approx(1.0)
    mods = [("jit_fn", 1.0, 2.0), ("jit_other", 3.0, 3.5),
            ("jit_fn", 4.0, 5.0)]
    assert T.kernel_seconds(mods, ["jit_fn"], 0, 4.5) == pytest.approx(1.5)
    host = [("bench.window", 0.0, 10.0), ("bench.pump", 0.5, 4.2),
            ("bench.wait", 5.0, 9.0)]
    split = dict((n, v) for n, v in T.idle_by_host(dev, host, 0.0, 10.0))
    assert split["bench.pump"] == pytest.approx(0.5 + 1.5)
    assert split["bench.wait"] == pytest.approx(4.0)
    assert split["bench.window"] == pytest.approx(0.5 + 1.0)
    assert sum(split.values()) == pytest.approx(10.0 - 2.5)
    assert T.top_programs(mods, 0, 10)[0] == ["jit_fn", 2.0]


def test_module_name_drops_the_launch_id():
    assert T.module_name("jit_fn(1234)") == "jit_fn"
    assert T.module_name("jit_fn") == "jit_fn"


def test_recorded_trace():
    """A window recorded on a TPU v5e (hg38-scan, a few launches),
    reduced to the event lists `read_xplane` returns."""
    rec = json.loads((DATA / "trace_v5e_scan.json").read_text())
    lo, hi = T.window_of(rec["host"])
    busy = T.busy_seconds(rec["device"], lo, hi)
    assert 0 < busy <= hi - lo
    # brute force on a 100 ns grid, computed when the trace was cut
    assert busy == pytest.approx(rec["expect"]["busy_s_grid"], rel=1e-3)
    kern = T.kernel_seconds(rec["modules"], ["jit_fn"], lo, hi)
    assert kern == pytest.approx(rec["expect"]["eval_kernel_s"], rel=1e-9)
    split = T.idle_by_host(rec["device"], rec["host"], lo, hi)
    assert sum(v for _, v in split) == pytest.approx(hi - lo - busy)


def test_cpu_trace_is_read(tmp_path):
    """`read_xplane` on a trace the profiler writes here: the harness's
    annotations come back on the host clock with the program's ops when
    the run is named a CPU run, and a TPU run's trace without a device
    plane is refused."""
    import jax
    import jax.numpy as jnp
    from harness.driver import annotate
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with annotate("bench.window"):
        for _ in range(3):
            with annotate("bench.pump"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    tr = T.read_xplane(path, "cpu")
    lo, hi = T.window_of(tr["host"])
    assert sum(n == "bench.pump" for n, _, _ in tr["host"]) == 3
    assert 0 < T.busy_seconds(tr["device"], lo, hi) <= hi - lo
    with pytest.raises(ValueError, match="no device plane"):
        T.read_xplane(path, "tpu")      # host events are never device time


def test_eval_count_at_paper_bfv():
    """4 atoms x 256 rows of one column at n=4096, K=2, D=4."""
    c = roofline.eval_launch_cost(n=4096, towers=2, digits=4, atoms=4,
                                  rows=256)
    row = 2 * 4096 * 4 + 2 * 4
    assert c["bytes"] == 256 * row + 8 * 2 * 4096 * 4 \
        + 4 * 2 * 2 * 4096 * 4 + 4 * 256 * 8
    assert c["ops"] == 4 * 256 * 2 * 8 * 4096 * 4 * 2
    t = roofline.least_seconds(c, {"hbm_bytes_per_s": 819e9,
                                   "int8_ops_per_s": 393e12})
    assert t["bytes_s"] > t["ops_s"]          # bound by bytes
    assert t["seconds"] == pytest.approx(c["bytes"] / 819e9)
    assert roofline.digits_per_tower(31, 8) == 4
