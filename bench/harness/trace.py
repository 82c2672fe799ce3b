"""The reduction from a profiler trace to device metrics.

`read_xplane` turns the `.xplane.pb` that `jax.profiler` writes into
plain event lists, on the profiler's one clock:

  device    per chip, [(name, start_s, end_s)] of the ops on its op
            line (`XLA Ops`), the events whose union is the time the
            chip was busy
  modules   per chip, [(name, start_s, end_s)] of its program line
            (`XLA Modules`): one event per launch of a compiled
            program, named as the trace names it with any `(id)` suffix
            cut
  host      [(name, start_s, end_s)] of the harness's own annotations
            (names starting `bench.`)

`reduce_window` makes the whole cell's numbers from the per-chip lists
by one rule: a time in which a chip is busy or idle is the mean over
chips (`busy_s`, with `busy_s_by_chip` beside it; the idle split by
host activity), and device time spent in programs is summed over chips
(`top_programs`; `kernel_seconds` per chip, summed by its reader).
With one chip each is the one plane's number itself.

The rest works on those lists alone, so a small recorded trace tests it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import math
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"
_SUFFIX = re.compile(r"\(\d+\)$")
_DEVICE_ID = re.compile(r":(\d+)$")


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


def read_xplane(path: str, platform: str, chips: int = 1
                ) -> Dict[str, object]:
    """The trace at `path` as `read_profile` reads it."""
    from jax.profiler import ProfileData
    return read_profile(ProfileData.from_file(path), platform, chips)


def read_profile(pd, platform: str, chips: int = 1) -> Dict[str, object]:
    """Device ops and program launches of `chips` chips, the harness's
    annotations, and the names of the planes and lines of the profile
    `pd` (a `jax.profiler.ProfileData`), for a run on `platform` (JAX's
    name).  A TPU run's chips are the first `chips` `/device:` planes
    with an op or program line, in device-id order (`read`: their
    names), and a trace with fewer is refused.  On `cpu` (the harness's
    own tests; no CPU time is a device time) the one chip's ops are the
    CPU client's events on the host plane, pooled over its devices.
    """
    out = {"device": [], "modules": [], "host": [], "planes": {},
           "read": []}
    found = []
    cpu_ops: List[tuple] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out["planes"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            if {OPS_LINE, MODULES_LINE} & {ln.name for ln in lines}:
                found.append((_device_id(plane.name), plane.name, lines))
        elif plane.name == "/host:CPU":
            for ln in lines:
                out["host"] += [ev for ev in _events(ln)
                                if ev[0].startswith("bench.")]
                if ln.name.startswith(CPU_CLIENT_LINE):
                    cpu_ops += _cpu_events(ln)
    if platform == "cpu":
        out["device"] = [[(n, s, e) for n, s, e, _ in cpu_ops]]
        out["modules"] = [[(m, s, e) for _, s, e, m in cpu_ops if m]]
        out["read"] = [f"/host:CPU {CPU_CLIENT_LINE}"]
        return out
    if len(found) < chips:
        raise ValueError(
            f"a {platform} trace with {len(found) or 'no'} device plane"
            f"{'' if len(found) == 1 else 's'} holding {OPS_LINE!r} or "
            f"{MODULES_LINE!r}, {chips} chips asked (a chip that ran no "
            f"op while traced has no such plane): planes "
            f"{sorted(out['planes'])}")
    for _, name, lines in sorted(found, key=lambda f: f[0])[:chips]:
        ops, modules = [], []
        for ln in lines:
            if ln.name == OPS_LINE:
                ops = _events(ln)
            elif ln.name == MODULES_LINE:
                modules = [(module_name(n), s, e) for n, s, e in _events(ln)]
        out["device"].append(ops or list(modules))
        out["modules"].append(modules)
        out["read"].append(name)
    return out


def _device_id(plane: str) -> float:
    """The device id a plane's name ends in (`/device:TPU:3` -> 3); a
    name without one sorts after every numbered plane."""
    m = _DEVICE_ID.search(plane)
    return int(m.group(1)) if m else math.inf


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


def idle_stretches(device: Sequence[Event], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no op ran on the device."""
    idle, t = [], lo
    for s, e in union([(s, e) for _, s, e in device], lo, hi):
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < hi:
        idle.append((t, hi))
    return idle


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
    return _largest(tot, k)


def idle_by_host(device: Sequence[Event], host: Sequence[Event], lo: float,
                 hi: float, k: int = 10) -> List[list]:
    """The device's idle time in [lo, hi], split by what the host was
    doing: each idle stretch is charged, piece by piece, to the harness
    annotation covering it (`bench.pump`, `bench.submit`, ...; the
    harness is one thread, so they do not overlap), or to
    `bench.window` between them.  The `k` largest, in seconds."""
    return _largest(_idle_split(device, host, lo, hi), k)


def _idle_split(device: Sequence[Event], host: Sequence[Event], lo: float,
                hi: float) -> Dict[str, float]:
    """`idle_by_host`'s whole split, by annotation name."""
    idle = idle_stretches(device, lo, hi)
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
    return tot


def _largest(tot: Dict[str, float], k: int) -> List[list]:
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def mean_by_key(splits: Sequence[Dict]) -> Dict:
    """The mean over chips of per-chip splits (a key a chip lacks counts
    0 there), keys in the order they first appear."""
    keys = dict.fromkeys(k for d in splits for k in d)
    return {k: sum(d.get(k, 0.0) for d in splits) / len(splits)
            for k in keys}


def reduce_window(tr: Dict[str, object], lo: float, hi: float,
                  k: int = 10) -> Dict[str, object]:
    """The whole cell's device numbers over [lo, hi] from `read_profile`'s
    per-chip lists (see the module docstring): `busy_s`,
    `busy_s_by_chip`, `window_s`, `top_programs` (device time summed
    over chips) and `idle_by_host` (each chip's split, averaged; with
    every entry it sums to `window_s - busy_s`)."""
    by_chip = [busy_seconds(d, lo, hi) for d in tr["device"]]
    launches = [ev for modules, device in zip(tr["modules"], tr["device"])
                for ev in modules or device]
    idle = mean_by_key([_idle_split(d, tr["host"], lo, hi)
                        for d in tr["device"]])
    return {"busy_s": sum(by_chip) / len(by_chip), "busy_s_by_chip": by_chip,
            "window_s": hi - lo, "top_programs": top_programs(launches, lo,
                                                              hi, k),
            "idle_by_host": _largest(idle, k)}
