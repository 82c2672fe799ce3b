"""One run of one cell: set up, warm up, measure, check, report.

`run` returns the result line's object; `run.py` prints it.  The steps:

  1. the cell, its configuration and its mix, found by name;
  2. the device (a TPU with the chips the cell asks for);
  3. set-up, timed as `setup_s` and split into phases: plaintext data
     and the schedule from the seed, then `deploy.build` (the
     configuration's builder in `bench/deploys/`);
  4. the window: `--seconds` of the schedule driven through the served
     path (with `--trace 1`, under the profiler and with `repro.obs`
     recording), programs compiled inside it counted, host stalls
     logged;
  5. the drain: what was due and not sent goes out, every answer is
     awaited (a minute past the close at most), every write's key is
     read back; then the device's peak bytes are read and the
     program's state freed;
  6. the comparison with the plain reference, and the metrics, each
     from its reader in `bench/metrics/`.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import time
from typing import Callable, Dict, List, Optional

from harness import deploy, device, reference, spec, traffic
from harness.driver import Window, annotate
from harness.meter import compile_meter
from harness.roofline import digits_per_tower

TRACE_DIR = spec.BENCH_DIR / ".trace"
LATENCY_CAP_MS = 1e9     # a failed request's latency as printed


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: str
    traced: bool
    window: Window
    setup_s: float
    phases: Dict[str, dict]
    config: dict
    mix: dict
    peaks: Optional[Dict[str, float]]
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    histograms: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None
    notes: List[str] = dataclasses.field(default_factory=list)
    platform: str = "tpu"
    chips: int = 1

    @property
    def sizes(self) -> Dict[str, int]:
        """The configuration's ring sizes, for operation counts."""
        c = self.config
        return {"n": int(c["n"]), "towers": int(c["num_towers"]),
                "digits": digits_per_tower(int(c["modulus_bits"]),
                                           int(c["gadget_log_base"]))}

    @property
    def trace_window(self):
        """(start, end) of the traced window on the profiler's clock."""
        from harness.trace import window_of
        return window_of(self.trace["host"]) if self.trace else None


def _log(line: str) -> None:
    import sys
    print(line, file=sys.stderr, flush=True)


def run(cell: str, seed: int, seconds: float, traced: bool, *,
        root=spec.ROOT, require_tpu: bool = True,
        config_override: Optional[dict] = None,
        mix_override: Optional[dict] = None,
        control: bool = False,
        log: Callable[[str], None] = _log) -> dict:
    """One run of `cell` (see the module docstring).  `control` runs
    the configuration's control in the program's place."""
    bench = spec.load_benchmark(root)
    wl = spec.workload(bench, cell)
    cfg = config_override or spec.config(bench, wl["config"], root)
    mix = mix_override or spec.traffic(wl["traffic"])
    dev = device.check(int(wl["chips"]), require_tpu=require_tpu)
    peaks = device.load_peaks(dev["kind"]) if require_tpu else None
    chips = int(wl["chips"])
    import jax
    log(f"cell {cell}: config {cfg['name']}, traffic {wl['traffic']}, "
        f"seed {seed}, {seconds}s, trace {int(traced)}; device "
        f"{dev['platform']} {dev['kind']} x{dev['count']}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    meter = compile_meter()

    t_setup = time.perf_counter()
    phases: Dict[str, dict] = {}
    data = deploy.table_data(cfg, mix["tables"], seed)
    bases = {t: data[t]["values"] for t in mix["tables"]}
    schedule = traffic.make_schedule(mix, data, seed, seconds)
    if control:
        from harness.targets import ControlTarget
        target = ControlTarget(bases, cfg["control"])
    else:
        target = deploy.build(cfg, mix, data, schedule, seed, phases, log,
                              lambda: device.peak_bytes(chips))
    setup_s = time.perf_counter() - t_setup
    log(f"setup_s {setup_s:.3f}: " + ", ".join(
        f"{k} {v['wall_s']:.3f}s ({v['compile_s']:.3f}s compile, "
        f"{v['programs']} programs, {v['cache_hits']} cache hits)"
        for k, v in phases.items()))

    window = Window(schedule, target)
    log(f"gc: {len(gc.get_objects())} objects tracked at the window's "
        f"opening, thresholds {gc.get_threshold()}")
    ctx = Context(cell, traced, window, setup_s, phases, cfg, mix, peaks,
                  platform=dev["platform"], chips=chips)
    programs0 = meter.snapshot()[0]
    if traced:
        _traced_window(window, seconds, ctx)
    else:
        with annotate("bench.window"):
            window.run(seconds)
    compiled = meter.snapshot()[0] - programs0
    window.drain()
    if traced:
        import jax
        jax.profiler.stop_trace()     # after the drain: it can take long
        _read_trace(ctx)
    if window.exhausted:
        log(f"window: {window.exhausted} clients sent every request the "
            "mix draws before the close")
    for line in window.stalls.lines():
        log(line)
    log(f"window: {seconds}s offered, closed after "
        f"{window.end - window.t0:.3f}s; {compiled} programs compiled "
        "inside it")
    peak = device.peak_bytes(chips)
    target.close()
    del target
    gc.collect()

    checks = reference.compare(window.records, bases)
    recs = window.window_records()
    lat = sorted(l for l in (r["sent"] - r["due"] for r in recs))
    log(f"generator lateness over {len(lat)} sends: p50 "
        f"{_pct(lat, 50) * 1e3:.3f} ms, p99 {_pct(lat, 99) * 1e3:.3f} ms, "
        f"max {lat[-1] * 1e3 if lat else 0:.3f} ms")
    for line in _backlog_lines(recs, window.t0):
        log(line)
    metrics = {}
    for m in spec.metrics_for(bench, cell, traced):
        value = spec.load_module("metrics", m["name"]).read(ctx)
        if value is None:
            if traced:
                log(f"metric {m['name']}: nothing to read")
                continue
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if math.isinf(value):
            value = LATENCY_CAP_MS
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if traced:      # the tracing overhead: these against an untraced run
        for m in spec.metrics_for(bench, cell, False):
            if m["name"] != "setup_s":
                v = spec.load_module("metrics", m["name"]).read(ctx)
                log(f"traced window {m['name']}: {v} {m['unit']}")
    for note in ctx.notes:
        log(note)
    failed = sum(r["status"] != reference.OK for r in recs)
    dev_out = {**dev, "memory_peak_bytes": peak}
    out = {"correct": all(v == 0 for v in checks.values()),
           "attempted": len(recs), "failed": failed, "metrics": metrics,
           "device": dev_out}
    if traced and ctx.trace is not None:
        dev_out["busy_s"] = ctx.trace["busy_s"]
        dev_out["busy_s_by_chip"] = ctx.trace["busy_s_by_chip"]
        dev_out["window_s"] = ctx.trace["window_s"]
        out["breakdown"] = {"device_ops": ctx.trace["top_programs"],
                            "idle_gaps": ctx.trace["idle_by_host"]}
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k}: {v} (limit 0)")
    return out


def _backlog_lines(recs: List[dict], t0: float) -> List[str]:
    """The most requests in flight at once (sent, not yet answered), and
    the requests that did not end OK by status and reason, with when
    they were due: a backlog that reached the loop's admission caps
    shows here."""
    events = sorted([(r["sent"], 1) for r in recs]
                    + [(r["done"], -1) for r in recs
                       if r["done"] is not None])
    most, at, depth = 0, 0.0, 0
    for t, step in events:
        depth += step
        if depth > most:
            most, at = depth, t
    out = [f"in flight: at most {most} requests at once, at "
           f"+{at - t0:.3f}s"]
    bad: Dict[tuple, List[float]] = {}
    for r in recs:
        if r["status"] != reference.OK:
            reason = (r["error"] or "").split(" (")[0]
            bad.setdefault((r["status"], reason), []).append(r["due"] - t0)
    for (status, reason), dues in sorted(bad.items()):
        out.append(f"not OK: {len(dues)} {status} ({reason or '-'}), due "
                   f"+{min(dues):.3f}s to +{max(dues):.3f}s")
    return out


def _pct(xs: List[float], p: float) -> float:
    from harness.driver import nearest_rank
    v = nearest_rank(xs, p)
    return 0.0 if v is None else v


def _traced_window(window: Window, seconds: float, ctx: Context) -> None:
    """The window under the profiler (left running: the caller stops
    it after the drain), with `repro.obs` recording the window alone."""
    import jax

    from repro import obs
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR)
    jax.profiler.start_trace(str(TRACE_DIR))
    with obs.tracing() as tracer:
        with annotate("bench.window"):
            window.run(seconds)
    ctx.spans = list(tracer.spans)
    snap = obs.REGISTRY.snapshot()
    ctx.counters = {k: v for k, v in snap.items() if isinstance(v, int)}
    ctx.histograms = {k: _histogram(obs.REGISTRY, k)
                      for k, v in snap.items() if isinstance(v, dict)}


def _histogram(registry, flat: str) -> List[float]:
    """The observations of the histogram the registry's snapshot names
    `name{k=v,...}`."""
    name, _, inner = flat.partition("{")
    labels = dict(kv.split("=", 1) for kv in inner.rstrip("}").split(",")
                  if kv)
    return list(registry.histogram(name, **labels).values)


def _read_trace(ctx: Context) -> None:
    """Reduce the traced window's profile, every chip of the cell
    (after the drain, so reading it delays no answer)."""
    from harness import trace as T
    try:
        tr = T.read_xplane(T.find_xplane(str(TRACE_DIR)), ctx.platform,
                           ctx.chips)
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    span = T.window_of(tr["host"])
    ctx.notes.append("trace planes: " + "; ".join(
        f"{p} [{', '.join(ls[:6])}]" for p, ls in tr["planes"].items()))
    if span is None or not any(tr["device"]):
        raise RuntimeError("the trace holds no window annotation or no "
                           "device op: " + ctx.notes[-1])
    tr.update(T.reduce_window(tr, *span))
    ctx.notes.append("busy by chip (s): " + ", ".join(
        f"{name} {b:.6f}" for name, b in zip(
            tr["read"], tr["busy_s_by_chip"])))
    ctx.trace = tr
