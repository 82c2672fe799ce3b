"""The reduction from trace to metrics: busy union, idle share, kernel
time, idle split by host activity, on a small recorded trace kept with
the benchmark and on hand-made profiles of several chips, and the
eval's operations and bytes from shapes."""
import json
import pathlib
import types

import pytest

from harness import roofline
from harness import spans as S
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
    assert len(tr["device"]) == len(tr["modules"]) == 1    # one pool
    assert 0 < T.busy_seconds(tr["device"][0], lo, hi) <= hi - lo
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


def test_recorded_trace_read_as_one_chip_repeats_the_one_plane_numbers():
    """The window of the recorded trace as a one-chip cell reads it
    gives exactly the floats of the one-plane reduction that came before
    chips were read (kept beside the trace)."""
    rec = json.loads((DATA / "trace_v5e_scan.json").read_text())
    old = json.loads((DATA / "trace_v5e_scan_one_plane.json").read_text())
    tr = {"device": [rec["device"]], "modules": [rec["modules"]],
          "host": rec["host"]}
    lo, hi = T.window_of(rec["host"])
    got = T.reduce_window(tr, lo, hi)
    assert got["busy_s"] == old["busy_s"]
    assert got["busy_s_by_chip"] == [old["busy_s"]]
    assert got["top_programs"] == old["top_programs"]
    assert got["idle_by_host"] == old["idle_by_host"]
    kern = sum(T.kernel_seconds(m, ["jit_fn"], lo, hi)
               for m in tr["modules"])
    assert kern == old["kernel_seconds.jit_fn"]


US = 1000       # ns


def _profile(device_planes, host):
    """A profile of `/device:` planes {name: {line: [(op, start_us,
    end_us)]}} and a host plane of harness annotations."""
    names = {}

    def line(i, name, events):
        evs = "".join(
            f"events {{ metadata_id: {names.setdefault(n, len(names) + 1)}"
            f" offset_ps: {int(s * US * 1000)} duration_ps: "
            f"{int((e - s) * US * 1000)} }} " for n, s, e in events)
        return f'lines {{ id: {i} name: "{name}" timestamp_ns: 0 {evs}}} '

    def plane(i, name, lines):
        body = "".join(line(j + 1, ln, evs)
                       for j, (ln, evs) in enumerate(lines.items()))
        meta = "".join(
            f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }} '
            for n, k in names.items())
        return f'planes {{ id: {i} name: "{name}" {body}{meta}}} '

    text = "".join(plane(i + 1, name, lines)
                   for i, (name, lines) in enumerate(device_planes.items()))
    text += plane(99, "/host:CPU", {"python": host})
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


HOST = [("bench.window", 0, 100), ("bench.pump", 10, 60),
        ("bench.wait", 70, 90)]
CHIP_OPS = {            # busy (us in [0, 100]) 30, 50, 0, 20
    0: [("fusion", 0, 20), ("fusion", 15, 30)],
    1: [("gather", 20, 70)],
    2: [],
    3: [("fusion", 80, 100)],
}
CHIP_MODULES = {
    0: [("jit_fn(7)", 0, 30)],
    1: [("jit_gather(9)", 20, 70)],
    2: [],
    3: [("jit_fn(7)", 80, 100)],
}


def _chips(order):
    return {f"/device:TPU:{i}": {"XLA Modules": CHIP_MODULES[i],
                                 "XLA Ops": CHIP_OPS[i]} for i in order}


@pytest.mark.parametrize("chips", [2, 4])
def test_several_chips_reduced_to_the_average_chip(chips):
    """Per-chip busy unions, their mean as `busy_s`, each chip's idle
    split by host annotation averaged (summing to window - busy), and
    programs summed over chips; planes are taken in device-id order,
    whatever order the profile lists them in."""
    planes = {"/device:TPU:9": {"XLA Ops": [("late", 0, 100)]},
              "/device:TPU:0 Idle": {"Steps": [("s", 0, 1)]},
              **_chips(reversed(range(4)))}
    tr = T.read_profile(_profile(planes, HOST), "tpu", chips)
    assert tr["read"] == [f"/device:TPU:{i}" for i in range(chips)]
    lo, hi = T.window_of(tr["host"])
    got = T.reduce_window(tr, lo, hi)
    busy = [30e-6, 50e-6, 0.0, 20e-6][:chips]
    assert got["busy_s_by_chip"] == pytest.approx(busy)
    assert got["busy_s"] == pytest.approx(sum(busy) / chips)
    assert got["window_s"] == pytest.approx(100e-6)
    split = dict(got["idle_by_host"])
    assert sum(split.values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    # chip 0 idles 30-60 under bench.pump, chip 1 10-20; per chip, then
    # averaged: bench.pump (30 + 10 + 50 + 50) / 4 us on four chips
    pump = [30e-6, 10e-6, 50e-6, 50e-6][:chips]
    assert split["bench.pump"] == pytest.approx(sum(pump) / chips)
    progs = dict(got["top_programs"])
    assert progs["jit_fn"] == pytest.approx(
        (30e-6 + (20e-6 if chips == 4 else 0.0)))
    assert progs["jit_gather"] == pytest.approx(50e-6)
    kern = sum(T.kernel_seconds(m, ["jit_fn"], lo, hi)
               for m in tr["modules"])
    assert kern == pytest.approx(progs["jit_fn"])


def test_program_spans_split_each_chip_and_average():
    """The idle split by innermost program span averages the chips'
    splits, so it sums to the window less the mean busy time."""
    tr = T.read_profile(_profile(_chips(range(4)), HOST), "tpu", 4)
    lo, hi = T.window_of(tr["host"])
    tr.update(T.reduce_window(tr, lo, hi))
    span = types.SimpleNamespace
    ctx = span(trace=tr, trace_window=(lo, hi), notes=[],
               window=span(t0=lo, end=hi),
               spans=[span(name="serve.pump", t0=10e-6, t1=60e-6, sid=1,
                           parent_sid=-1)])
    out = S.split(ctx)
    assert out["idle_s"] == pytest.approx(tr["window_s"] - tr["busy_s"])
    assert out["by_name"]["serve.pump"] == pytest.approx(
        (30e-6 + 10e-6 + 50e-6 + 50e-6) / 4)


def test_tpu_trace_short_of_chips_is_refused():
    planes = {"/device:TPU:0": {"XLA Ops": CHIP_OPS[0]},
              "/device:TPU:1": {"Steps": [("s", 0, 1)]}}
    prof = _profile(planes, HOST)
    assert len(T.read_profile(prof, "tpu", 1)["device"]) == 1
    with pytest.raises(ValueError, match="1 device plane holding"):
        T.read_profile(prof, "tpu", 2)
    with pytest.raises(ValueError, match="no device plane"):
        T.read_profile(_profile({}, HOST), "tpu", 1)
