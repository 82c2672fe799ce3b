"""Run the served encrypted query path once on the TPU and check it.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded path on four chips

One chip: profile paper-bfv (n=4096, two towers), the full-noise gadget
comparison key, and all 34,423 rows of the paper's hg38 column, served
through `ServeLoop` -> `QueryServer`: range scans, indexed point lookups,
a top-k, and an insert followed by reads that must see it.  Four chips:
the same column as a 4-shard `ShardedTable` with its index on a 4-device
mesh, behind `ShardedQueryServer`.  Every answer is checked exactly
against numpy on the plaintext (`repro.db.smoke`).

Earlier lines carry the phase timings (compile and run apart), programs
compiled, peak device memory, the lane tile and the tables' rows.  The
last line is {"ok": true, "device": {...}}, printed only when every
phase passed on a TPU.  Any failure, or a platform other than TPU,
exits non-zero.
"""
import argparse
import json
import os
import sys

# Rows of the one-chip index table: the first rows of the hg38 column.
# The full column (8 GiB of ciphertext at paper-bfv, padded to 65,536
# rows) leaves too little of a v5e's 16 GB for a sorted copy of all of
# it beside the sort network's intermediates.
INDEX_ROWS = 8192


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} "
              "present", file=sys.stderr)
        return 2
    from repro.db import smoke

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    log = lambda line: print(line, flush=True)  # noqa: E731
    if args.chips == 1:
        record = smoke.run_smoke("paper-bfv", "gadget", rows=None,
                                 index_rows=INDEX_ROWS, log=log)
    else:
        record = smoke.run_sharded_smoke("paper-bfv", "gadget", rows=None,
                                         shards=4, devices=4, log=log)
    print("summary: " + json.dumps(smoke.summary(record)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
