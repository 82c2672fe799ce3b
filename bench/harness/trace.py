"""The reduction from a profiler trace to device metrics.

`read_xplane` turns the `.xplane.pb` that `jax.profiler` writes into
plain event lists, on the profiler's one clock:

  device    [(name, start_s, end_s)] of the ops on the first traced
            device's op line (`XLA Ops`), the events whose union is the
            time the device was busy
  modules   [(name, start_s, end_s)] of its program line (`XLA
            Modules`): one event per launch of a compiled program,
            named as the trace names it with any `(id)` suffix cut
  host      [(name, start_s, end_s)] of the harness's own annotations
            (names starting `bench.`)

The rest works on those lists alone, so a small recorded trace tests it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """A program's name without the trace's `(id)` suffix."""
    return _SUFFIX.sub("", name)


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under `trace_dir`."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str, platform: str) -> Dict[str, object]:
    """Device ops, program launches and harness annotations of the
    trace at `path`, plus the names of its planes and lines, for a run
    on `platform` (JAX's name).  A TPU run's ops come from the first
    `/device:` plane with an op or program line, and a trace without one
    is refused.  On `cpu` (the harness's own tests; no CPU time is a
    device time) the ops are the CPU client's events on the host plane.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"device": [], "modules": [], "host": [], "planes": {}}
    device_seen = False
    cpu_ops: List[tuple] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out["planes"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:") and not device_seen:
            names = {ln.name for ln in lines}
            if OPS_LINE not in names and MODULES_LINE not in names:
                continue
            device_seen = True
            for ln in lines:
                if ln.name == OPS_LINE:
                    out["device"] = _events(ln)
                elif ln.name == MODULES_LINE:
                    out["modules"] = [(module_name(n), s, e)
                                      for n, s, e in _events(ln)]
            if not out["device"]:
                out["device"] = list(out["modules"])
        elif plane.name == "/host:CPU":
            for ln in lines:
                out["host"] += [ev for ev in _events(ln)
                                if ev[0].startswith("bench.")]
                if ln.name.startswith(CPU_CLIENT_LINE):
                    cpu_ops += _cpu_events(ln)
    if platform == "cpu":
        out["device"] = [(n, s, e) for n, s, e, _ in cpu_ops]
        out["modules"] = [(m, s, e) for _, s, e, m in cpu_ops if m]
    elif not device_seen:
        raise ValueError(f"a {platform} trace with no device plane holding "
                         f"{OPS_LINE!r} or {MODULES_LINE!r}: planes "
                         f"{sorted(out['planes'])}")
    return out


def _cpu_events(line) -> List[tuple]:
    out = []
    for ev in line.events:
        if ev.duration_ns <= 0:
            continue
        stats = dict(ev.stats)
        out.append((ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9,
                    str(stats.get("hlo_module", ""))))
    return out


def _events(line) -> List[Event]:
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """The union of `intervals` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(device: Sequence[Event], lo: float, hi: float) -> float:
    """Seconds in [lo, hi] during which some op ran on the device."""
    return sum(e - s for s, e in union([(s, e) for _, s, e in device],
                                       lo, hi))


def window_of(host: Sequence[Event]) -> Optional[Tuple[float, float]]:
    """The span of the harness's `bench.window` annotation."""
    spans = [(s, e) for n, s, e in host if n == "bench.window"]
    return spans[0] if spans else None


def kernel_seconds(modules: Sequence[Event], names: Sequence[str],
                   lo: float, hi: float) -> float:
    """Summed device time, inside [lo, hi], of the launches of the
    programs called `names`."""
    want = set(names)
    return sum(min(e, hi) - max(s, lo) for n, s, e in modules
               if n in want and min(e, hi) > max(s, lo))


def top_programs(modules: Sequence[Event], lo: float, hi: float,
                 k: int = 10) -> List[list]:
    """The `k` programs with the most device time in [lo, hi]."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in modules:
        if min(e, hi) > max(s, lo):
            tot[n] += min(e, hi) - max(s, lo)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_by_host(device: Sequence[Event], host: Sequence[Event], lo: float,
                 hi: float, k: int = 10) -> List[list]:
    """The device's idle time in [lo, hi], split by what the host was
    doing: each idle stretch is charged, piece by piece, to the harness
    annotation covering it (`bench.pump`, `bench.submit`, ...; the
    harness is one thread, so they do not overlap), or to
    `bench.window` between them.  The `k` largest, in seconds."""
    busy = union([(s, e) for _, s, e in device], lo, hi)
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < hi:
        idle.append((t, hi))
    inner = sorted((s, e, n) for n, s, e in host if n != "bench.window")
    starts = [s for s, _, _ in inner]
    tot: Dict[str, float] = collections.defaultdict(float)
    for a, b in idle:
        i = max(0, bisect.bisect_right(starts, a) - 1)
        x = a
        while x < b:
            if i < len(inner) and inner[i][1] <= x:
                i += 1
            elif i < len(inner) and inner[i][0] <= x:
                y = min(b, inner[i][1])
                tot[inner[i][2]] += y - x
                x = y
            else:
                y = min(b, inner[i][0]) if i < len(inner) else b
                tot["bench.window"] += y - x
                x = y
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]
