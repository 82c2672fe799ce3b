"""The device's idle time split by the program's own spans.

Every `repro.obs` span is also a profiler annotation under its own
name, with its `sid` and its parent's (`parent`, -1 at a root) as event
stats.  A program event here is `(name, start_s, end_s, sid, parent)`,
and `idle_by_span` charges each stretch in which no op ran on the
device to the innermost (deepest) program span open over it, or to
`(none)`.

The run's reduction (`harness.trace.read_xplane`) keeps only the
harness's own annotations before the trace is deleted, so the metric
readers take the program's spans from the `Tracer` (`ctx.spans`, timed
on `time.perf_counter`, the harness's own clock) and place them on the
profiler's clock by the window: the `bench.window` annotation opens and
closes within microseconds of `Window.t0` and `Window.end`
(`from_tracer`).  Each reader logs the split once (`split`), with how
far the placed `serve.pump` spans stray outside the harness's
`bench.pump` annotations, the check that the two clocks agree.
"""
from __future__ import annotations

import bisect
import collections
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

from harness.trace import idle_stretches, mean_by_key

ProgramEvent = Tuple[str, float, float, int, int]
NONE = "(none)"
PUMP = "serve.pump"
TOP = 12


def from_tracer(spans, t0: float, t1: float, lo: float,
                hi: float) -> List[ProgramEvent]:
    """The `Tracer`'s spans as program events, their times mapped
    linearly from [t0, t1] on their clock onto [lo, hi]."""
    scale = (hi - lo) / (t1 - t0) if t1 > t0 else 1.0
    return [(s.name, lo + (s.t0 - t0) * scale, lo + (s.t1 - t0) * scale,
             s.sid, s.parent_sid) for s in spans]


def depths(program: Sequence[ProgramEvent]) -> Dict[int, int]:
    """Each span's depth below the outermost span of `program` that
    holds it (a span whose parent is not in `program` is at 0)."""
    parent = {sid: p for _, _, _, sid, p in program}
    out: Dict[int, int] = {}
    for sid in parent:
        chain = []
        while sid in parent and sid not in out:
            chain.append(sid)
            sid = parent[sid]
        d = out.get(sid, -1)
        for s in reversed(chain):
            d += 1
            out[s] = d
    return out


def idle_by_sid(device, program: Sequence[ProgramEvent], lo: float,
                hi: float) -> Dict[Optional[int], float]:
    """Idle seconds in [lo, hi] by the sid of the innermost program span
    open over them (deepest; of equals, the latest started), None where
    no span is open.  The parts sum to the idle time."""
    idle = idle_stretches(device, lo, hi)
    starts = [a for a, _ in idle]
    before = [0.0]                         # idle seconds before stretch i
    for a, b in idle:
        before.append(before[-1] + b - a)

    def idle_until(x: float) -> float:
        i = bisect.bisect_right(starts, x)
        if i == 0:
            return 0.0
        a, b = idle[i - 1]
        return before[i - 1] + min(x, b) - a

    depth = depths(program)
    marks = []                             # (time, 1 open / 0 close, sid)
    for _, s, e, sid, _ in program:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            marks += [(s, 1, sid), (e, 0, sid)]
    marks.sort()
    tot: Dict[Optional[int], float] = collections.defaultdict(float)
    heap: list = []                        # (-depth, -start, sid)
    closed = set()
    t = lo
    for x, kind, sid in marks + [(hi, 0, None)]:
        while heap and heap[0][2] in closed:
            heapq.heappop(heap)
        if x > t:
            owner = heap[0][2] if heap else None
            tot[owner] += idle_until(x) - idle_until(t)
            t = x
        if kind:
            heapq.heappush(heap, (-depth.get(sid, 0), -x, sid))
        elif sid is not None:
            closed.add(sid)
    return dict(tot)


def idle_by_span(device, program: Sequence[ProgramEvent], lo: float,
                 hi: float) -> List[list]:
    """Idle seconds in [lo, hi] by the name of the innermost program span
    open over them, or `(none)`, largest first; they sum to the idle
    time."""
    tot = _by_name(program, idle_by_sid(device, program, lo, hi))
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])]


def _by_name(program: Sequence[ProgramEvent],
             by_sid: Dict[Optional[int], float]) -> Dict[str, float]:
    name = {sid: n for n, _, _, sid, _ in program}
    tot: Dict[str, float] = collections.defaultdict(float)
    for sid, v in by_sid.items():
        tot[name.get(sid, NONE)] += v
    return dict(tot)


def _below(program: Sequence[ProgramEvent], ancestor: str) -> set:
    """The sids of the spans strictly below a span named `ancestor`."""
    parent = {sid: p for _, _, _, sid, p in program}
    name = {sid: n for n, _, _, sid, _ in program}
    out = set()
    for sid in parent:
        p = parent[sid]
        while p in parent:
            if name[p] == ancestor:
                out.add(sid)
                break
            p = parent[p]
    return out


def _stray(program: Sequence[ProgramEvent], host) -> Optional[float]:
    """The most any `serve.pump` span reaches outside the `bench.pump`
    annotation nearest it, in seconds (None without either)."""
    pumps = sorted((s, e) for n, s, e in host if n == "bench.pump")
    starts = [s for s, _ in pumps]
    worst = None
    for n, s, e, _, _ in program:
        if n != PUMP or not pumps:
            continue
        i = bisect.bisect_right(starts, s)
        # the pump it starts in, or the next one where it starts a
        # hair before that one's annotation
        off = min(max(0.0, a - s, e - b)
                  for a, b in pumps[max(0, i - 1):i + 1])
        worst = off if worst is None else max(worst, off)
    return worst


def split(ctx) -> Optional[dict]:
    """The traced window's idle time by innermost program span, each
    chip's split averaged over the chips (as `busy_s` is), computed
    once per run and logged to `ctx.notes`: `by_name` (name -> s),
    `window_s`, `idle_s`, and the idle seconds under `bench.pump` and
    under a span below `serve.pump`.  None without a trace or spans."""
    if getattr(ctx, "idle_split", None) is not None:
        return ctx.idle_split
    if not ctx.trace or not ctx.trace_window or not ctx.spans:
        return None
    lo, hi = ctx.trace_window
    w = ctx.window
    program = from_tracer(ctx.spans, w.t0, w.end, lo, hi)
    by_sid = mean_by_key([idle_by_sid(dev, program, lo, hi)
                          for dev in ctx.trace["device"]])
    below = _below(program, PUMP)
    out = {"by_name": _by_name(program, by_sid), "window_s": hi - lo,
           "idle_s": sum(by_sid.values()),
           "below_pump_s": sum(v for sid, v in by_sid.items()
                               if sid in below),
           "bench_pump_s": sum(v for n, v in ctx.trace.get(
               "idle_by_host", []) if n == "bench.pump")}
    ctx.idle_split = out
    ctx.notes += _table(out, _stray(program, ctx.trace["host"]))
    return out


def _table(out: dict, stray: Optional[float]) -> List[str]:
    idle = out["idle_s"] or 1.0
    lines = [f"device idle by innermost program span: {out['idle_s']:.6f}s"
             f" idle of {out['window_s']:.6f}s (top {TOP}; s, % of idle)"]
    for n, v in sorted(out["by_name"].items(), key=lambda x: -x[1])[:TOP]:
        lines.append(f"  {n:<24} {v:12.6f}  {100 * v / idle:7.3f}%")
    pump = out["bench_pump_s"]
    share = (f"{100 * out['below_pump_s'] / pump:.3f}%" if pump
             else "no idle under bench.pump")
    lines.append(f"  idle under bench.pump {pump:.6f}s; under a span below "
                 f"{PUMP} {out['below_pump_s']:.6f}s ({share})")
    if stray is not None:
        lines.append(f"  clock check: {PUMP} spans reach at most "
                     f"{stray * 1e6:.1f} us outside their bench.pump")
    return lines


def idle_share(ctx, names: Sequence[str]) -> Optional[float]:
    """100 * the idle seconds whose innermost span is named in `names`,
    over the traced window; None where no such span was ever the
    innermost one (or nothing was traced)."""
    out = split(ctx)
    if out is None or not any(n in out["by_name"] for n in names):
        return None
    return 100.0 * sum(out["by_name"].get(n, 0.0)
                       for n in names) / out["window_s"]
