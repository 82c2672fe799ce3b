"""Tiny copies of the benchmark's configurations and mixes, at the
`test-bfv` profile, for CPU tests of the harness, and one run of a
cell's tiny copy (`run`).

A cell that asks for more chips than the test process has devices runs
in a child process on that many virtual CPU devices, since JAX fixes
its device count when it starts:

    python3 bench/tests/tiny.py --root <checkout> --workload <cell> \
        --seed <n> --seconds <s> [--trace 1] [--control 1] [--rate r] \
        [--trace-dir <dir>]

prints the result line as `bench/run.py` does, with the harness's look
for a TPU skipped.
"""
import argparse
import copy
import json
import os
import pathlib
import subprocess
import sys

TINY_ROWS = {"hg38": 300, "hg38_prefix": 64, "usertable": 128}
OTHER_ROWS = 256        # a table TINY_ROWS does not name
HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent


def tiny_config(cfg: dict) -> dict:
    """`cfg` at test-bfv widths with a few hundred rows."""
    c = copy.deepcopy(cfg)
    c.update(profile="test-bfv", n=256, num_towers=1, modulus_bits=31,
             gadget_log_base=6, plaintext_modulus=257)
    for t in c["tables"]:
        rows = TINY_ROWS.get(t["name"], OTHER_ROWS)
        if "recordcount" in t:
            t["recordcount"] = rows
            t["key_bits"] = 13
        else:
            t["rows"] = rows
        if "t" in t:
            t["t"] = 257
    return c


def tiny_mix(mix: dict, rate: float = 40.0) -> dict:
    """`mix` with a rate the CPU keeps up with at test-bfv."""
    m = copy.deepcopy(mix)
    for st in m["streams"]:
        if "rate_per_s" in st:
            st["rate_per_s"] = rate
        else:
            st["rounds"] = 3000
    return m


def run(name: str, seed: int, seconds: float, traced: bool = False, *,
        control: bool = False, rate: float = 40.0, root=None,
        trace_dir=None, log=lambda line: None) -> dict:
    """The result line of one run of cell `name`'s tiny copy (the cell
    as `root`'s `BENCHMARK.json` has it), in this process where it has
    the devices the cell asks for, else in a child process on that many
    virtual CPU devices (whose log ends the error when it fails)."""
    import jax

    from harness import spec
    root = pathlib.Path(root or spec.ROOT)
    chips = int(spec.workload(spec.load_benchmark(root), name)["chips"])
    if chips <= len(jax.devices()):
        return _run_here(name, seed, seconds, traced, control, rate, root,
                         trace_dir, log)
    argv = [sys.executable, str(HERE / "tiny.py"), "--root", str(root),
            "--workload", name, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(int(traced)), "--control",
            str(int(control)), "--rate", str(rate)]
    if trace_dir is not None:
        argv += ["--trace-dir", str(trace_dir)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={chips}").strip())
    p = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=600)
    if p.returncode != 0:
        raise RuntimeError(f"tiny copy of {name} on {chips} virtual "
                           f"devices exited {p.returncode}: "
                           f"{p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def _run_here(name, seed, seconds, traced, control, rate, root, trace_dir,
              log) -> dict:
    from harness import cell, spec
    bench = spec.load_benchmark(root)
    wl = spec.workload(bench, name)
    kept = cell.TRACE_DIR
    if trace_dir is not None:
        cell.TRACE_DIR = pathlib.Path(trace_dir)
    try:
        return cell.run(name, seed, seconds, traced, root=root,
                        require_tpu=False,
                        config_override=tiny_config(spec.config(
                            bench, wl["config"], root)),
                        mix_override=tiny_mix(spec.traffic(wl["traffic"]),
                                              rate=rate),
                        control=control, log=log)
    finally:
        cell.TRACE_DIR = kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=40.0)
    ap.add_argument("--trace-dir")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    out = _run_here(args.workload, args.seed, args.seconds,
                    bool(args.trace), bool(args.control), args.rate,
                    pathlib.Path(args.root), args.trace_dir,
                    lambda line: print(line, file=sys.stderr, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
