"""Drives one measured window: submits each request when it is due,
pumps the serving loop between submissions, and records every answer.

One thread does it all, as a front end in front of `ServeLoop` would:
submit what is due, pump one scheduling round, hand back what came
out.  Every call into a layer sits in a `jax.profiler.TraceAnnotation`
(`bench.submit`, `bench.pump`, `bench.collect`, `bench.wait`), so that
a traced run can say what the host was doing while the device idled.

Times are the harness's own `time.perf_counter`.  A request is timed
from when its stream's arrival process (`bench/traffic/<loop>.py`) made
it due: an open loop's arrival time, a closed-loop client's last answer.
Calls that hold the thread long, and garbage collections, are logged
(`harness.stalls`), so that a tail can be traced to its cause.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, List, Optional

from harness import spec
from harness.reference import OK, PENDING
from harness.stalls import StallLog


def annotate(name: str):
    """A host span in the profiler's trace (cheap when no trace runs)."""
    try:
        import jax
        return jax.profiler.TraceAnnotation(name)
    except ImportError:                       # pragma: no cover
        return contextlib.nullcontext()


class Window:
    """The requests of one window and what became of them."""

    def __init__(self, schedule, target, clock=time.perf_counter):
        self.schedule = schedule
        self.target = target
        self.clock = clock
        self.records: List[dict] = []          # in submission order
        self._inflight: Dict[int, dict] = {}
        self.t0 = self.close = self.end = 0.0
        self.exhausted = 0
        self.stalls = StallLog(clock)
        by_stream = collections.defaultdict(list)
        for req in schedule.requests:
            by_stream[req.stream].append(req)
        self.sources = [
            spec.load_module("traffic", st["loop"]).Source(by_stream[i])
            for i, st in enumerate(schedule.streams)]

    # -- submission and collection ----------------------------------------

    def _submit(self, req, due: float) -> dict:
        began = self.stalls.begin()
        sent = began[0]
        ticket = self.target.submit(req)
        self.stalls.call("submit", began, req.op)
        rec = {"rid": req.rid, "op": req.op, "values": req.values,
               "table": req.table, "stream": req.stream, "of": req.of,
               "due": due, "sent": sent, "done": None, "status": PENDING,
               "row_ids": None, "error": "", "readback": req.of is not None,
               "client": req.client}
        self.records.append(rec)
        self._inflight[ticket] = rec
        return rec

    def _collect(self) -> List[dict]:
        done = []
        for ticket in list(self._inflight):
            out = self.target.take(ticket)
            if out is None:
                continue
            rec = self._inflight.pop(ticket)
            rec["status"], rec["row_ids"], rec["done"], rec["error"] = out
            done.append(rec)
        return done

    def _pump(self) -> List[dict]:
        began = self.stalls.begin()
        with annotate("bench.pump"):
            self.target.pump()
        with annotate("bench.collect"):
            done = self._collect()
        self.stalls.call("pump", began, ", ".join(
            f"{n} {op}" for op, n in collections.Counter(
                r["op"] for r in done).items()))
        return done

    # -- the window --------------------------------------------------------

    def run(self, seconds: float) -> None:
        """Offer the schedule for `seconds` s: send what each stream has
        due, run one scheduling round while anything is pending, else
        wait for the next request due.  Returns at the first moment past
        the close with no scheduling round running."""
        self.t0 = self.clock()
        self.close = self.t0 + seconds
        for src in self.sources:
            src.start(self.t0)
        with self.stalls:
            while True:
                now = self.clock()
                if now >= self.close:
                    break
                with annotate("bench.submit"):
                    for src in self.sources:
                        for req, due in src.due(now):
                            self._submit(req, due)
                if self.target.pending():
                    for rec in self._pump():
                        self.sources[rec["stream"]].answered(rec)
                    continue
                nxt = [t for t in (s.next_due() for s in self.sources)
                       if t is not None]
                with annotate("bench.wait"):
                    time.sleep(max(0.0, min(nxt + [self.close])
                                   - self.clock()))
        self.end = self.clock()
        self.exhausted = sum(s.exhausted() for s in self.sources)

    def drain(self, timeout_s: float = 60.0) -> None:
        """After the close: send what was due in the window and not yet
        sent, wait for every answer (at most `timeout_s` past the
        close), then read back every write's key."""
        limit = self.close + timeout_s
        for src in self.sources:
            for req, due in src.late():
                self._submit(req, due)
        while self._inflight and self.clock() < limit:
            self._pump()
        for req in self.schedule.readback:
            self._submit(req, self.clock())
        while self._inflight and self.clock() < limit + timeout_s:
            self._pump()

    # -- what the metrics read -------------------------------------------

    def window_records(self) -> List[dict]:
        """Records of the requests due in the window (not read-backs)."""
        return [r for r in self.records if not r["readback"]]

    def completed_in_window(self) -> List[dict]:
        """Records answered OK by the close."""
        return [r for r in self.window_records()
                if r["status"] == OK and r["done"] <= self.close]

    def answered_by_end(self) -> List[dict]:
        """Records answered OK by the end of the window's last scheduling
        round (the work that counters of the window cover)."""
        return [r for r in self.window_records()
                if r["status"] == OK and r["done"] <= self.end]


def latency_ms(rec: dict, close: float) -> float:
    """A request's latency from due to answer, in ms: still unanswered
    at the close, the time it had waited by then; failed or refused,
    infinite (it misses any limit)."""
    if rec["status"] == OK and rec["done"] is not None \
            and rec["done"] <= close:
        return (rec["done"] - rec["due"]) * 1e3
    if rec["status"] in (OK, PENDING):
        return max(0.0, close - rec["due"]) * 1e3
    return float("inf")


def nearest_rank(values: List[float], p: float) -> Optional[float]:
    """The p-th percentile (0..100) by nearest rank; None if empty."""
    if not values:
        return None
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, -(-int(p * len(xs)) // 100) - 1))
    return xs[k]
