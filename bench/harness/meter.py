"""Set-up split into phases: compile and run seconds, programs compiled,
persistent-cache hits and peak device bytes per phase.

`CompileMeter` is a copy of `repro.db.smoke.CompileMeter` (JAX's
monitoring events), kept here so that the yardstick does not move with
the program.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

# lowering to MLIR and the backend compile (tracing is left out: nested
# jits trace inside their caller's trace, so its events overlap)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Programs compiled and seconds spent compiling, process-wide, from
    JAX's monitoring events (a persistent-cache hit counts as a hit, not
    a compiled program)."""

    def __init__(self):
        import jax
        self.programs = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs
            if event == _COMPILE_EVENTS[-1]:
                self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        """(programs compiled, compile seconds, cache hits) so far."""
        return self.programs, self.compile_s, self.cache_hits


_METER: Optional[CompileMeter] = None


def compile_meter() -> CompileMeter:
    """The process's one meter (JAX listeners cannot be removed)."""
    global _METER
    if _METER is None:
        _METER = CompileMeter()
    return _METER


@contextlib.contextmanager
def phase(name: str, record: Dict[str, dict], log: Callable[[str], None],
          peak: Callable[[], Optional[int]] = lambda: None):
    """Time one phase; record and log wall, compile and run seconds,
    programs compiled, cache hits and peak device bytes."""
    meter = compile_meter()
    p0, c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    p1, c1, h1 = meter.snapshot()
    rec = {"wall_s": wall, "compile_s": c1 - c0, "run_s": wall - (c1 - c0),
           "programs": p1 - p0, "cache_hits": h1 - h0,
           "peak_bytes_in_use": peak()}
    record[name] = rec
    log(f"phase {name}: wall {wall:.3f}s = compile {rec['compile_s']:.3f}s"
        f" + run {rec['run_s']:.3f}s; {rec['programs']} programs compiled,"
        f" {rec['cache_hits']} cache hits;"
        f" peak_bytes_in_use {rec['peak_bytes_in_use']}")
