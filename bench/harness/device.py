"""The device a run measures, and its published peaks.

A run names its device as JAX reports it and refuses anything that is
not a TPU, or fewer chips than the cell asks for.  Peaks come from
`bench/peaks.json`, keyed by `device_kind`; a kind missing there is an
error, never a default.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Optional

from harness.spec import BENCH_DIR


class NoDevice(RuntimeError):
    """No accelerator of the kind and count a cell needs."""


def load_peaks(kind: str, path: pathlib.Path = BENCH_DIR / "peaks.json"
               ) -> Dict[str, float]:
    """The peaks of `kind` (KeyError names the kinds the table holds)."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in {path.name} "
                       f"(it holds {sorted(table)})")
    return {k: float(v) for k, v in table[kind].items()}


def check(chips: int, *, require_tpu: bool = True) -> Dict[str, object]:
    """The devices JAX sees, as the result line's `device` entry.
    Raises `NoDevice` when `require_tpu` and they are not `chips` TPUs
    or more."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoDevice(f"no TPU: JAX platform is {dev.platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"{chips} chips asked, {len(devices)} present")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peak_bytes(chips: int) -> Optional[int]:
    """`peak_bytes_in_use` on the fullest of the first `chips` devices
    (None where the backend reports no memory statistics)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
