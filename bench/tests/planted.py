"""A fault planted in the program inside the child process that runs a
multi-chip cell's tiny copy (`tiny.run` starts such a child, and a
test's monkeypatch does not reach it), then that tiny copy on as many
virtual CPU devices:

    python3 bench/tests/planted.py --fault <name> <tiny.py arguments>

prints the tiny copy's result line.  `run` starts it and returns the
line.  Fault `none` plants nothing.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent


def _fanout_positions_shifted():
    """Every fan-out search's sorted positions one further on."""
    from repro.db.shard.index import ShardedIndex
    search = ShardedIndex.search

    def shifted(self, *a, **k):
        pos = search(self, *a, **k)
        return np.minimum(pos + 1, self.counts[:, None])
    ShardedIndex.search = shifted


def _shard_row_ids_offset():
    """Shard 1's rows answer under the next row's id."""
    from repro.db.shard.table import ShardedTable
    gids = ShardedTable.shard_slot_gids

    def offset(self, s):
        ids = gids(self, s)
        if s == 1:
            ids = np.where(ids >= 0, np.minimum(ids + 1, self.n_total - 1),
                           ids)
        return ids
    ShardedTable.shard_slot_gids = offset


FAULTS = {"none": lambda: None,
          "fanout_positions_shifted": _fanout_positions_shifted,
          "shard_row_ids_offset": _shard_row_ids_offset}


def run(fault: str, name: str, seed: int, seconds: float, chips: int, *,
        root=None, timeout: float = 300) -> dict:
    """The result line of cell `name`'s tiny copy run on `chips` virtual
    CPU devices in a child process with `fault` planted."""
    from harness import spec
    argv = [sys.executable, str(HERE / "planted.py"), "--fault", fault,
            "--root", str(root or spec.ROOT), "--workload", name, "--seed",
            str(seed), "--seconds", str(seconds)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={chips}").strip())
    p = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"{name} with fault {fault} exited "
                           f"{p.returncode}: {p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--fault")
    fault = argv.pop(i + 1)
    argv.pop(i)
    sys.path[:0] = [str(BENCH), str(HERE), str(BENCH.parent / "src")]
    FAULTS[fault]()
    import tiny
    return tiny.main(argv)


if __name__ == "__main__":
    sys.exit(main())
