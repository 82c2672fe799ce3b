"""The chip benchmark: one run of one cell, one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  A cell is a `workloads` entry of
`BENCHMARK.json`; see `bench/harness/cell.py` for what a run does.
Earlier lines (standard error) carry the set-up split into phases, the
tables, generator lateness, programs compiled in the window and the
numbers compared with their limits; the last line of standard output is
the result: `correct`, `attempted`, `failed`, `metrics`, `device`
(`busy_s`, the mean over the cell's chips, `busy_s_by_chip` and
`window_s` when traced), `breakdown` when traced, and `checks`, each
compared number beside its limit.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not in the
checkout.  JAX's persistent compilation cache lives in `.jax_cache/` at
the root of the checkout, so only a checkout's first run compiles.
"""
import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    from harness import cell, device
    try:
        out = cell.run(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except device.NoDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
