"""Where the harness's one thread stood still during the window.

A request waits while the thread is inside a call into the system
(`submit`, `pump`) or inside Python's garbage collector.  `StallLog`
records every such call of `MIN_CALL_S` or more, with the process's CPU
seconds over it (near the wall time: the host computed; far below it:
the thread waited, on the device or for a core), and every collection
of `MIN_GC_S` or more, by generation.  `lines` sums them up for the
run's earlier lines.
"""
from __future__ import annotations

import gc
import time
from typing import Callable, List

MIN_CALL_S = 0.25
MIN_GC_S = 0.01


class StallLog:
    """Long calls and garbage collections inside the window."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.t0 = 0.0
        self.calls: List[tuple] = []     # (kind, at_s, wall_s, cpu_s, what)
        self.gcs: List[tuple] = []       # (generation, at_s, wall_s)
        self.gc_s = 0.0
        self._gc_start = None
        self.active = False

    def __enter__(self):
        self.t0 = self.clock()
        self.active = True
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._gc)
        self.active = False

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = self.clock()
        elif self._gc_start is not None:
            dur = self.clock() - self._gc_start
            self.gc_s += dur
            if dur >= MIN_GC_S:
                self.gcs.append((info.get("generation"),
                                 self._gc_start - self.t0, dur))
            self._gc_start = None

    def begin(self) -> tuple:
        """The moment a call starts, for `call`."""
        return self.clock(), time.process_time()

    def call(self, kind: str, began: tuple, what: str) -> None:
        """Note one call into the system, from `began` to now."""
        end, cpu = self.clock(), time.process_time()
        if self.active and end - began[0] >= MIN_CALL_S:
            self.calls.append((kind, began[0] - self.t0, end - began[0],
                               cpu - began[1], what))

    def lines(self, k: int = 10) -> List[str]:
        """The summary for the run's earlier lines: the `k` longest calls
        and collections."""
        out = [f"host stalls: {len(self.calls)} calls of >= {MIN_CALL_S}s, "
               f"{len(self.gcs)} garbage collections of >= {MIN_GC_S}s "
               f"({self.gc_s:.3f}s in all collections)"]
        for kind, at, wall, cpu, what in sorted(
                self.calls, key=lambda c: -c[2])[:k]:
            out.append(f"  {kind} at +{at:.3f}s: {wall:.3f}s wall, "
                       f"{cpu:.3f}s process cpu; answered: {what or '-'}")
        for gen, at, wall in sorted(self.gcs, key=lambda g: -g[2])[:k]:
            out.append(f"  gc generation {gen} at +{at:.3f}s: {wall:.3f}s")
        return out
