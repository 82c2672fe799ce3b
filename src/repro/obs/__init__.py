"""`repro.obs`: tracing, metrics, and launch accounting in one layer.

The engine's cost story — eval launches, compare lanes, index probes,
jit retraces, batch latency — flows through this package so the
planner, the serving loop, and the benchmarks all hang measurements on
the same counters.  Three pieces:

  * spans  (`obs.span`, `obs.tracing`) — nested host spans, each also
    a `jax.profiler.TraceAnnotation` carrying its `sid`/`parent`, so a
    profiler trace puts them on one clock with the device plane (device
    time comes from that plane); Chrome-trace export; near-zero cost
    when disabled;
  * metrics (`obs.count`, `obs.observe`, `obs.metrics.REGISTRY`) —
    counters + histograms that absorb the per-call stats dataclasses;
  * jitwatch (`obs.jit_launch`) — launch-signature sets per site,
    surfacing pow2-bucketing violations as a `jit.retraces` counter.

Enable for a region with `with obs.tracing() as tr:` (or process-wide
via `REPRO_OBS=1`); everything is a one-bool-check no-op otherwise.
"""
from repro.obs import export, jitwatch, metrics
from repro.obs.export import (bench_fields, chrome_trace, metrics_dump,
                              profiler_spans, validate_chrome_trace,
                              write_chrome_trace, write_metrics)
from repro.obs.jitwatch import launch as jit_launch
from repro.obs.jitwatch import retraces as jit_retraces
from repro.obs.jitwatch import signatures as jit_signatures
from repro.obs.metrics import (REGISTRY, Counter, Histogram, Registry,
                               absorb_batch_stats, absorb_compaction_stats,
                               absorb_exec_stats, absorb_join_stats, count,
                               observe)
from repro.obs.trace import (TRACER, Span, Tracer, current_span, disable,
                             enable, get_tracer, is_enabled, span, tracing)

__all__ = [
    "export", "jitwatch", "metrics",
    "bench_fields", "chrome_trace", "metrics_dump", "profiler_spans",
    "validate_chrome_trace",
    "write_chrome_trace", "write_metrics",
    "jit_launch", "jit_retraces", "jit_signatures",
    "REGISTRY", "Counter", "Histogram", "Registry",
    "absorb_batch_stats", "absorb_compaction_stats",
    "absorb_exec_stats", "absorb_join_stats", "count", "observe",
    "TRACER", "Span", "Tracer", "current_span", "disable", "enable",
    "get_tracer", "is_enabled", "span", "tracing",
]
