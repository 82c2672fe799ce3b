"""The device's idle time split by program span (`harness.spans`): the
sweep on hand events, the Tracer's spans placed on the profiler's clock
against the same spans read from the profiler's trace, and traced CPU
runs of each cell reporting the split's metrics."""
import time

import pytest

import tiny
from harness import spans as S
from harness import trace as T

SEED = 2**31 + 4321


def test_idle_charged_to_the_innermost_span():
    dev = [("op", 2.0, 3.0), ("op", 6.0, 7.0)]
    program = [("serve.pump", 1.0, 9.0, 1, -1),
               ("index.search", 1.5, 8.0, 2, 1),
               ("index.step", 1.5, 4.0, 3, 2),
               ("index.decode", 3.5, 4.0, 4, 3),
               ("index.step", 4.0, 8.0, 5, 2)]
    split = dict((n, v) for n, v in S.idle_by_span(dev, program, 0.0, 10.0))
    assert split[S.NONE] == pytest.approx(1.0 + 1.0)
    assert split["serve.pump"] == pytest.approx(0.5 + 1.0)
    assert split["index.step"] == pytest.approx(0.5 + 0.5 + 2.0 + 1.0)
    assert split["index.decode"] == pytest.approx(0.5)
    assert "index.search" not in split      # a child covers it throughout
    assert sum(split.values()) == pytest.approx(
        10.0 - T.busy_seconds(dev, 0.0, 10.0))
    assert S.depths(program) == {1: 0, 2: 1, 3: 2, 4: 3, 5: 2}


def test_idle_clipped_to_the_window_and_summed():
    dev = [("a", 0.0, 1.0), ("b", 2.5, 3.0), ("c", 2.8, 4.0)]
    program = [("root", -1.0, 2.0, 7, 3),     # parent outside: a root
               ("late", 3.5, 12.0, 8, -1)]
    split = dict((n, v) for n, v in S.idle_by_span(dev, program, 0.5, 5.0))
    assert split == pytest.approx({"root": 1.0, S.NONE: 0.5, "late": 1.0})
    assert S.idle_by_span([], [], 0.0, 2.0) == [[S.NONE, 2.0]]


def test_tracer_spans_placed_on_the_profiler_clock(tmp_path):
    """The Tracer's spans, mapped by the window's ends, land where the
    profiler's trace puts the same spans (matched by sid)."""
    import jax

    from harness.driver import annotate
    from repro import obs
    f = jax.jit(lambda x: x + 1)
    x = jax.numpy.ones(16)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with annotate("bench.window"):
        t0 = time.perf_counter()
        with obs.tracing() as tr:
            for _ in range(3):
                with obs.span("serve.pump"):
                    with obs.span("index.step"):
                        f(x).block_until_ready()
                        with obs.span("index.decode"):
                            time.sleep(0.002)
                time.sleep(0.002)
        t1 = time.perf_counter()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    lo, hi = T.window_of(T.read_xplane(path, "cpu")["host"])
    seen = {e["sid"]: (e["start_ns"] * 1e-9,
                       (e["start_ns"] + e["dur_ns"]) * 1e-9)
            for e in obs.profiler_spans(tmp_path)}
    placed = S.from_tracer(tr.spans, t0, t1, lo, hi)
    assert len(placed) == len(seen) == 9
    name_of = {sid: n for n, _, _, sid, _ in placed}
    up = {"serve.pump": None, "index.step": "serve.pump",
          "index.decode": "index.step"}
    for name, s, e, sid, parent in placed:
        assert abs(s - seen[sid][0]) < 2e-4 and abs(e - seen[sid][1]) < 2e-4
        assert name_of.get(parent) == up[name]


CELLS = {"hg38-point": (1.0, ["probe_idle_pct.lookup"]),
         "ycsb-d-latest": (2.0, ["probe_idle_pct.op", "write_idle_pct.op"]),
         "hg38-scan": (1.0, ["tile_idle_pct.scan"])}


@pytest.mark.parametrize("name", list(CELLS))
def test_traced_run_splits_idle_by_span(name, tmp_path):
    # a trace directory of its own: another test file's traced run may
    # run beside this one, in another process
    seconds, want = CELLS[name]
    lines = []
    out = tiny.run(name, SEED, seconds, True, trace_dir=tmp_path / "trace",
                   log=lines.append)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    idle = next(v["value"] for k, v in m.items()
                if k.startswith("device_idle_pct."))
    for k in want:
        assert 0 < m[k]["value"] <= idle, (k, m)
    table = [ln for ln in lines
             if ln.startswith("device idle by innermost program span")]
    assert len(table) == 1                  # logged once per run
    assert any("under a span below serve.pump" in ln for ln in lines)


def test_clock_check_takes_the_nearest_pump():
    """A `serve.pump` placed a hair before its `bench.pump` is measured
    against that pump, not the one before it."""
    host = [("bench.pump", 1.0, 2.0), ("bench.pump", 3.0, 4.0)]
    program = [("serve.pump", 1.1, 1.9, 1, -1),
               ("serve.pump", 3.0 - 2e-6, 3.9, 2, -1)]
    assert S._stray(program, host) == pytest.approx(2e-6)
    assert S._stray(program, []) is None
