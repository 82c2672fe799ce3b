"""The control of a cell: its plain reference in the program's place,
with one guarantee of the configuration broken (the configuration's
`control`: `approximate_values` or `stale_reads`), driven by the same
traffic at the cell's own size and judged by the same comparison.  It
has to come out `"correct": false`.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

It runs on the host alone; the benchmark's own runs never run it.
"""
import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    from harness import cell
    out = cell.run(args.workload, args.seed, args.seconds, False,
                   require_tpu=False, control=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
