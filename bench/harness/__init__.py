"""The chip benchmark's harness: everything a run needs that is not one
configuration, traffic mix or metric (those sit in files of their own
under `bench/`, found by the names in `BENCHMARK.json`)."""
